"""The reader of ``locate_native_share`` on a hand-built registry: two
batches inside the window (1, 10) with three ``workload.locate`` calls, and
one batch before it."""
import pytest

import tracing

SPANS = [
    ("serving.observe", 0.5, 1.5, None, 0),
    ("workload.locate", 0.6, 0.8, 0, 0),
    ("serving.observe", 2.0, 3.0, None, 2),
    ("trace.compile", 2.0, 2.4, 2, 2),
    ("workload.locate", 2.1, 2.35, 3, 2),
    ("serving.observe", 4.0, 5.0, None, 5),
    ("trace.compile", 4.0, 4.2, 5, 5),
    ("workload.locate", 4.0, 4.1, 6, 5),
    ("workload.locate", 4.1, 4.15, 6, 5),
]
NATIVE = [
    ("locate.native", 0.7, 1, 1),      # batch 0: before the window
    ("locate.native", 2.2, 1, 4),
    ("locate.native", 4.05, 1, 7),
    ("host_sync", 4.12, 1, 8),         # another counter
]


def _read(monkeypatch, counts, spans_=SPANS):
    import run
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: {
        "spans": spans_, "counts": counts, "dropped": 0})
    spans = tracing.Spans(annotate=False)
    spans.intervals[tracing.WINDOW_SPAN].append((1.0, 10.0))
    ctx = {"spans": spans, "device": {"platform": "tpu"}}
    return run.load_module("metrics", "locate_native_share").read(ctx)


@pytest.mark.parametrize("counts, share", [
    (NATIVE, 200 / 3),
    (NATIVE[3:], 0.0),                 # a program that counts no native call
])
def test_share_of_the_window_calls_that_ran_native(monkeypatch, counts,
                                                   share):
    assert _read(monkeypatch, counts) == pytest.approx(share)


def test_no_share_without_a_locate_call(monkeypatch):
    no_locate = [s if s[0] != "workload.locate" else ("trace.unpack",) + s[1:]
                 for s in SPANS]
    assert _read(monkeypatch, [], no_locate) is None
