"""Program spans and counters (``repro.obs``) on the serving loop.

Recording follows a running JAX profiler trace and nothing else: with no
trace the registry stays empty; under one, each served batch is one
``serving.observe`` root holding a fixed set of layer spans, retunes add
``serving.retune`` with the engine's spans inside, the spans also land on
the profiler's host plane, and device→host reads and compiles are counted
under the span that made them.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.cam import CamGeometry
from repro.core.session import System
from repro.serving import (ServingConfig, ServingSession,
                           synthetic_drifting_trace)
from repro.tuning.session import RMIBuilder, TuningSession

KEYS = np.sort(np.random.default_rng(0).uniform(0, 1e6, 8192))

#: Spans every served batch opens once, with the span each sits in.
PER_BATCH = {
    "trace.compile": "serving.observe",
    "trace.unpack": "trace.compile",
    "workload.locate": "trace.compile",
    "sketch.update": "serving.observe",
    "profile.route": "sketch.update",
    "profile.prep": "sketch.update",
    "profile.wait": "sketch.update",
    "sketch.chunk": "sketch.update",
    "serving.detect": "serving.observe",
}
#: ``profile.rows`` opens twice: the kernel's block split into rows, and
#: the rows restacked.
ROWS = ("profile.rows", "sketch.update")
#: Spans of a retune, with the span each sits in.
PER_RETUNE = {
    "serving.retune": "serving.observe",
    "sketch.merge": "serving.retune",
    "engine.price": "serving.retune",
    "price.marshal": "engine.price",
    "price.wait": "engine.price",
}


def _session():
    """A tiny drifting RMI deployment, profiled and priced on the device
    path (interpret mode off-TPU), started on the first 400 events."""
    events = synthetic_drifting_trace(KEYS, [
        {"events": 800, "mix": (1.0, 0.0, 0.0), "hot_center": 0.2,
         "hot_width": 0.05},
        {"events": 800, "mix": (1.0, 0.0, 0.0), "hot_center": 0.8,
         "hot_width": 0.05},
    ], seed=7)
    tuning = TuningSession(System(CamGeometry(c_ipp=64, page_bytes=4096),
                                  memory_budget_bytes=512 << 10))
    srv = ServingSession(
        tuning, RMIBuilder(KEYS), KEYS, overrides={"branch": (16, 64)},
        config=ServingConfig(batch_size=200, window_chunks=3,
                             drift_threshold=0.12, hysteresis=0.04,
                             cooldown_batches=1, profile_executor="device"))
    srv.start(events[:400])
    return srv, events[400:]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced ``observe`` over 6 batches: the session, its reports,
    the registry and the written ``.xplane.pb``."""
    out = tmp_path_factory.mktemp("trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_ENGINE_EXECUTOR", "device")
        srv, events = _session()
        obs.clear()
        with jax.profiler.trace(str(out)):
            reports = srv.observe(events)
        reg = obs.snapshot()
        obs.clear()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return srv, reports, reg, path


def _children(spans, root):
    """Names of the spans under ``root``, each with its parent's name."""
    return [(s[0], spans[s[3]][0]) for s in spans
            if s[4] == root and s[3] is not None]


def test_nothing_records_without_a_profiler(engine_executor):
    engine_executor("device")
    srv, events = _session()
    obs.clear()
    reports = srv.observe(events)
    assert len(reports) == 6
    assert obs.snapshot() == {"spans": [], "counts": [], "dropped": 0}


def test_each_batch_is_one_root_holding_every_layer_span(traced):
    srv, reports, reg, _ = traced
    spans = reg["spans"]
    assert reg["dropped"] == 0
    assert all(s[2] is not None and s[2] >= s[1] for s in spans)
    roots = [i for i, s in enumerate(spans) if s[3] is None]
    assert [spans[i][0] for i in roots] == ["serving.observe"] * len(reports)
    retuned = 0
    for root, report in zip(roots, reports):
        below = _children(spans, root)
        for name, parent in PER_BATCH.items():
            assert below.count((name, parent)) == 1, name
        assert below.count(ROWS) == 2
        if report.decision is None:
            assert not any(n in PER_RETUNE for n, _ in below)
            continue
        retuned += 1
        for name, parent in PER_RETUNE.items():
            assert below.count((name, parent)) == 1, name
    assert retuned == srv.stats.retune_evaluations >= 1


def test_host_reads_are_counted_under_their_spans(traced):
    _, reports, reg, _ = traced
    spans = reg["spans"]
    syncs = [spans[c[3]][0] for c in reg["counts"] if c[0] == "host_sync"]
    n_retunes = sum(r.decision is not None for r in reports)
    assert syncs.count("profile.wait") == len(reports)
    assert syncs.count("sketch.chunk") == len(reports)
    assert syncs.count("price.wait") == n_retunes
    # nothing is read before the launch; nd_i and best_id after the wait
    assert syncs.count("price.marshal") == 0
    assert syncs.count("engine.price") == 2 * n_retunes
    sync_bytes = [c for c in reg["counts"] if c[0] == "host_sync_bytes"]
    assert len(sync_bytes) == len(syncs)
    assert all(c[2] > 0 for c in sync_bytes)


def test_spans_land_on_the_profiler_host_plane(traced):
    _, reports, reg, path = traced
    data = jax.profiler.ProfileData.from_file(path)
    events = [(plane.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in data.planes for line in plane.lines
              for ev in line.events]
    lo = min(e[2] for e in events)
    hi = max(e[3] for e in events)
    names = set(PER_BATCH) | set(PER_RETUNE) | {"serving.observe", ROWS[0]}
    ours = [e for e in events if e[1] in names]
    assert {e[1] for e in ours} == names
    assert all(e[0].startswith("/host:") for e in ours)
    assert all(lo <= e[2] <= e[3] <= hi for e in ours)
    roots = sorted((a, b) for _, n, a, b in ours if n == "serving.observe")
    assert len(roots) == len(reports)
    for _, n, a, b in ours:
        if n == "trace.compile":
            assert any(r0 <= a and b <= r1 for r0, r1 in roots)


def test_to_host_counts_device_arrays_only(tmp_path):
    x = jnp.arange(6, dtype=jnp.float32)
    host = np.arange(3)
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("read"):
            got = obs.to_host(x, np.float64)
            same = obs.to_host(host)
        obs.count("rows", 3)
    counts = obs.snapshot()["counts"]
    obs.clear()
    want = np.asarray(x, np.float64)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert same.dtype == host.dtype and np.array_equal(same, host)
    ours = [(c[0], c[2], c[3]) for c in counts
            if c[0].startswith("host_sync") or c[0] == "rows"]
    assert ours == [("host_sync", 1, 0), ("host_sync_bytes", 24, 0),
                    ("rows", 3, None)]


def test_a_fresh_jit_counts_compile_under_its_span(tmp_path):
    x = jnp.ones(5)
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer"):
            with obs.span("inner"):
                jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
            jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    reg = obs.snapshot()
    obs.clear()
    by_span = [reg["spans"][c[3]][0] for c in reg["counts"]
               if c[0] == "compile"]
    assert by_span.count("inner") >= 1
    assert by_span.count("outer") >= 1
    assert set(by_span) == {"inner", "outer"}


def test_span_decorates_and_the_cap_drops_the_rest(tmp_path, monkeypatch):
    @obs.span("deco")
    def double(v):
        return 2 * v

    assert double(4) == 8                     # off: the plain call
    monkeypatch.setattr(obs, "MAX_RECORDS", 3)
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        results = [double(i) for i in range(5)]
    reg = obs.snapshot()
    obs.clear()
    assert results == [0, 2, 4, 6, 8]
    assert [s[0] for s in reg["spans"]] == ["deco"] * 3
    assert reg["dropped"] == 2
