"""The readers of the write cell's program spans (``write_spans.py``) on a
hand-built registry rooted at ``write.batch``."""
import pytest

import tracing
import write_spans

W = tracing.WINDOW_SPAN

#: Three ``write.batch`` batches, of which only two (2 and 10) lie inside
#: the window (1, 10): batch 0 starts before it, batch 18 ends after it.
SPANS = [
    ("write.batch", 0.5, 1.5, None, 0),
    ("sketch.update", 0.6, 0.8, 0, 0),
    ("write.batch", 2.0, 3.0, None, 2),
    ("trace.compile", 2.0, 2.2, 2, 2),
    ("sketch.update", 2.2, 2.4, 2, 2),
    ("profile.uniform", 2.25, 2.35, 4, 2),
    ("write.stage", 2.4, 2.45, 2, 2),
    ("write.price_event", 2.5, 2.9, 2, 2),
    ("write.burst", 2.5, 2.6, 7, 2),
    ("engine.price", 2.6, 2.8, 7, 2),
    ("write.batch", 4.0, 5.0, None, 10),
    ("trace.compile", 4.0, 4.1, 10, 10),
    ("sketch.update", 4.1, 4.3, 10, 10),
    ("write.price_event", 4.3, 4.9, 10, 10),
    ("write.burst", 4.3, 4.5, 13, 10),
    ("engine.price", 4.5, 4.7, 13, 10),
    ("write.decide", 4.9, 4.95, 10, 10),
    ("write.batch", 9.5, 10.5, None, 17),
]
COUNTS = [
    ("profile.lanes", 0.7, 5_000, 1),       # batch 0: before the window
    ("profile.lanes", 2.3, 5_000, 5),
    ("profile.pad_lanes", 2.3, 3_192, 5),
    ("profile.lanes", 2.55, 3_000, 8),
    ("profile.pad_lanes", 2.55, 1_096, 8),
    ("compile", 2.56, 2, 8),
    ("profile.lanes", 4.2, 5_100, 12),
    ("profile.pad_lanes", 4.2, 3_092, 12),
    ("compile", 6.0, 1, None),              # no span open
]
REGISTRY = {"spans": SPANS, "counts": COUNTS, "dropped": 0}


def test_write_batches_inside_the_window_are_read():
    prog = write_spans.WriteProgram(REGISTRY, (1.0, 10.0))
    assert prog.batches == 2
    assert {s[4] for s in prog.spans} == {2, 10}
    # 2,000 ms of batches less 300 ms compile, 400 ms update, 1,000 ms
    # price event: staging and the decision stay in the loop
    inner = ("trace.compile", "sketch.update", "write.price_event")
    assert prog.outside_ms(inner) == pytest.approx(150.0)
    assert write_spans.WriteProgram(REGISTRY, (20.0, 30.0)).outside_ms(
        inner) is None


@pytest.mark.parametrize("metric, value", [
    ("write_profile_ms", 200.0), ("burst_ms", 150.0),
    ("write_price_ms", 200.0), ("write_loop_ms", 150.0),
    ("write_compiles_per_batch", 1.0),
    ("write_pad_share", 100 * 7_380 / 20_480)])
def test_write_readers_read_the_windowed_registry(monkeypatch, metric,
                                                  value):
    import run
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: REGISTRY)
    spans = tracing.Spans(annotate=False)
    spans.intervals[W].append((1.0, 10.0))
    ctx = {"spans": spans, "device": {"platform": "tpu"}}
    assert run.load_module("metrics", metric).read(ctx) == pytest.approx(
        value)
    # off the chip, and with no batch in the window, every reader is silent
    assert run.load_module("metrics", metric).read(
        {"spans": spans, "device": {"platform": "cpu"}}) is None
    spans.intervals[W][0] = (20.0, 30.0)
    assert run.load_module("metrics", metric).read(ctx) is None


def test_pad_share_is_silent_for_a_program_that_counts_no_lanes(
        monkeypatch):
    import run
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: {
        "spans": SPANS, "counts": [c for c in COUNTS
                                   if not c[0].startswith("profile.")],
        "dropped": 0})
    spans = tracing.Spans(annotate=False)
    spans.intervals[W].append((1.0, 10.0))
    ctx = {"spans": spans, "device": {"platform": "tpu"}}
    assert run.load_module("metrics", "write_pad_share").read(ctx) is None
    assert run.load_module("metrics", "write_compiles_per_batch").read(
        ctx) == pytest.approx(1.0)
