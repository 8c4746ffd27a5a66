"""The readers of the program's own spans (``program_spans.py``) on a
hand-built registry and a synthetic trace."""
import pytest

import program_spans
import tracing

W = tracing.WINDOW_SPAN

#: Three ``serving.observe`` batches, of which only two (2 and 7) lie
#: inside the window (1, 10): batch 0 starts before it, batch 14 ends
#: after it, and span 15 is a root of another name (a warm-up update).
SPANS = [
    ("serving.observe", 0.5, 1.5, None, 0),
    ("trace.compile", 0.6, 0.8, 0, 0),
    ("serving.observe", 2.0, 3.0, None, 2),
    ("trace.compile", 2.0, 2.4, 2, 2),
    ("trace.unpack", 2.0, 2.1, 3, 2),
    ("workload.locate", 2.1, 2.35, 3, 2),
    ("sketch.update", 2.4, 2.9, 2, 2),
    ("serving.observe", 4.0, 5.0, None, 7),
    ("trace.compile", 4.0, 4.2, 7, 7),
    ("workload.locate", 4.0, 4.1, 8, 7),
    ("workload.locate", 4.1, 4.15, 8, 7),
    ("serving.retune", 4.2, 4.9, 7, 7),
    ("engine.price", 4.3, 4.8, 11, 7),
    ("price.marshal", 4.3, 4.5, 12, 7),
    ("serving.observe", 9.5, 10.5, None, 14),
    ("sketch.update", 6.0, 6.5, None, 15),
]
COUNTS = [
    ("host_sync", 0.7, 1, 1),          # batch 0: before the window
    ("host_sync", 2.85, 1, 6),
    ("host_sync_bytes", 2.85, 4096, 6),
    ("host_sync", 4.45, 1, 13),
    ("host_sync", 4.46, 1, 13),
    ("host_sync", 6.2, 1, 15),         # not a batch
    ("compile", 3.5, 1, None),         # no span open
]
REGISTRY = {"spans": SPANS, "counts": COUNTS, "dropped": 0}


def test_only_batches_inside_the_window_are_read():
    prog = program_spans.Program(REGISTRY, (1.0, 10.0))
    assert prog.batches == 2
    assert {s[4] for s in prog.spans} == {2, 7}
    assert prog.per_batch_ms("trace.unpack") == pytest.approx(50.0)
    # every call summed per batch, or one call's mean
    assert prog.per_batch_ms("workload.locate") == pytest.approx(200.0)
    assert prog.per_call_ms("workload.locate") == pytest.approx(400 / 3)
    assert prog.per_call_ms("price.marshal") == pytest.approx(200.0)
    assert prog.per_batch_ms("profile.prep") is None
    assert prog.per_call_ms("profile.prep") is None
    assert prog.per_batch_count("host_sync") == pytest.approx(1.5)
    assert prog.per_batch_count("host_sync_bytes") == pytest.approx(2048.0)
    assert prog.per_batch_count("compile") == 0.0


def test_time_outside_the_inner_spans():
    prog = program_spans.Program(REGISTRY, (1.0, 10.0))
    # 2,000 ms of batches less 400 + 200 ms compile, 500 ms update and
    # 500 ms price (the retune's other 200 ms stays in the loop)
    inner = ("trace.compile", "sketch.update", "engine.price")
    assert prog.outside_ms(inner) == pytest.approx(200.0)
    empty = program_spans.Program(REGISTRY, (20.0, 30.0))
    assert empty.batches == 0
    assert empty.outside_ms(inner) is None
    assert empty.per_batch_count("host_sync") is None


def test_program_reads_the_registry_on_the_chip_only(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: REGISTRY)
    spans = tracing.Spans(annotate=False)
    spans.intervals[W].append((1.0, 10.0))
    prog = program_spans.program({"spans": spans,
                                  "device": {"platform": "tpu"}})
    assert prog.batches == 2
    assert program_spans.program({"spans": spans,
                                  "device": {"platform": "cpu"}}) is None
    spans.intervals[W][0] = (20.0, 30.0)
    assert program_spans.program({"spans": spans,
                                  "device": {"platform": "tpu"}}) is None


@pytest.mark.parametrize("metric, value", [
    ("unpack_ms", 50.0), ("search_ms", 200.0), ("loop_ms", 200.0),
    ("price_marshal_ms", 200.0), ("host_syncs_per_batch", 1.5),
    ("host_sync_bytes_per_batch", 2048.0), ("profile_prep_ms", None)])
def test_readers_read_the_windowed_registry(monkeypatch, metric, value):
    import run
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: REGISTRY)
    spans = tracing.Spans(annotate=False)
    spans.intervals[W].append((1.0, 10.0))
    ctx = {"spans": spans, "device": {"platform": "tpu"}}
    got = run.load_module("metrics", metric).read(ctx)
    assert got == (None if value is None else pytest.approx(value))


def test_idle_with_no_program_span_open():
    # device busy [10, 30), [40, 45), [60, 70) of the window [0, 100)
    ops = {0: [("profile_grid", 10, 30), ("fusion", 40, 45),
               ("price_grid", 60, 70)]}
    host = [(W, 0, 100), ("serving.observe", 5, 50),
            ("trace.compile", 5, 9), ("serving.observe", 80, 95)]
    trace = tracing.Trace((0, 100), ops, host)
    # gaps: [0, 10) mid 5 in trace.compile; [30, 40) mid 35 in the first
    # batch; [45, 60) mid 52 in none; [70, 100) mid 85 in the second batch
    assert trace.idle_by_span() == {
        "trace.compile": pytest.approx(10e-9),
        "serving.observe": pytest.approx(40e-9),
        "outside spans": pytest.approx(15e-9)}
    assert program_spans.unattributed_share(trace) == pytest.approx(
        100 * 15 / 65)


def test_idle_share_needs_device_ops_and_program_spans(tmp_path):
    ops = {0: [("profile_grid", 10, 30)]}
    # a program without spans: every gap would read as unattributed
    bench_only = tracing.Trace((0, 100), ops, [(W, 0, 100),
                                               ("locate", 0, 50)])
    assert program_spans.unattributed_share(bench_only) is None
    no_device = tracing.Trace((0, 100), {}, [(W, 0, 100),
                                             ("serving.observe", 0, 50)])
    assert program_spans.unattributed_share(no_device) is None
    assert program_spans.idle_unattributed(str(tmp_path)) is None
