"""Shape-stable profiling on the write path.

A served batch's read/update split and its merge burst's window count
change every batch.  The uniform-eps profiler pads each part to a lane
bucket (``page_ref.pad_to_bucket``) with zero-weight lanes, so a serving
loop compiles once per bucket.  These tests pin the padded results to the
unpadded estimators, count the lanes, and check that a warmed
``WriteSession`` compiles nothing.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import page_ref
from repro.core.cam import CamGeometry
from repro.core.session import (CostSession, GridCandidate, System,
                                sorted_part_for)
from repro.core.workload import Workload
from repro.serving.trace import TraceEvent
from repro.write import (CamMergeScheduler, WriteConfig, WriteSession,
                         merge_burst_workload)

N, C_IPP = 60_000, 64
GEOM = CamGeometry(c_ipp=C_IPP, page_bytes=1024)
PAGES = GEOM.num_pages(N)
EPS = (8, 32, 100)


def _batch(n_reads, n_updates, seed=0):
    rng = np.random.default_rng(seed)
    parts = []
    if n_reads:
        parts.append(Workload.point(rng.integers(0, N, n_reads), n=N))
    if n_updates:
        parts.append(Workload.update(rng.integers(0, N, n_updates), n=N))
    return parts[0] if len(parts) == 1 else Workload.mixed(*parts)


@pytest.mark.parametrize("n", [0, 1, 255, 256, 257, 4_097])
def test_lanes_pad_to_a_power_of_two(n):
    lanes = page_ref.bucket_lanes(n)
    assert lanes >= max(n, page_ref.MIN_BUCKET)
    assert lanes & (lanes - 1) == 0
    assert lanes < 2 * max(n, page_ref.MIN_BUCKET)
    (a, b), n_valid = page_ref.pad_to_bucket(np.arange(n), np.arange(n) + 1)
    assert a.shape == b.shape == (lanes,) and a.dtype == np.int32
    assert int(n_valid) == n and not a[n:].any()


@pytest.mark.parametrize("n_reads, n_updates", [
    (500, 500), (900, 100), (100, 900), (0, 700), (700, 0),
    (255, 257), (256, 256), (257, 255)])
def test_bucketed_uniform_profile_equals_unpadded(n_reads, n_updates):
    """Read histograms, totals and E[DAC], and the write stream, equal the
    unpadded single-eps estimators within float32."""
    wl = _batch(n_reads, n_updates, seed=n_reads + 7 * n_updates)
    cost = CostSession(System(GEOM, memory_budget_bytes=64 * 1024))
    cands = [GridCandidate(knob=e, eps=e, size_bytes=1024.0) for e in EPS]
    prof = cost.grid_profiles(cands, wl, executor="host")
    reads = [p for p in (wl.parts if wl.kind == "mixed" else (wl,))]
    for k, eps in enumerate(EPS):
        want = np.zeros(PAGES)
        for p in reads:                 # reads and update locates alike
            c, _ = page_ref.point_page_refs(
                jax.numpy.asarray(p.positions, jax.numpy.int32), eps,
                C_IPP, PAGES)
            want += np.asarray(c, np.float64)
        got = np.asarray(prof.counts[k], np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
        assert prof.totals[k] == pytest.approx(want.sum(), rel=1e-5)
        dac = 1 + 2 * eps / C_IPP
        want_dac = (dac * n_reads + (dac + 1) * n_updates) / (n_reads
                                                             + n_updates)
        assert prof.dacs[k] == pytest.approx(want_dac, rel=1e-6)
    if not n_updates:
        assert prof.wparts == ()
        return
    upd = reads[-1].positions
    want_w = np.bincount(upd // C_IPP, minlength=PAGES).astype(np.float64)
    for wp in prof.wparts:
        np.testing.assert_array_equal(np.asarray(wp.counts, np.float64),
                                      want_w)
        assert wp.total_refs == n_updates


@pytest.mark.parametrize("n_staged", [1, 90, 255, 256, 257, 600, 3_000])
def test_bucketed_burst_equals_unpadded(n_staged):
    """The merge burst's (R, N, pinned, premise) and coverage equal the
    unpadded page_intervals + sorted_workload_stats, on both sides of a
    bucket edge of the window count."""
    rng = np.random.default_rng(n_staged)
    staged = rng.integers(0, N, n_staged)
    burst = merge_burst_workload(staged, N, C_IPP)
    sp = sorted_part_for(burst, 0, GEOM, PAGES)
    plo, phi = page_ref.page_intervals(
        jax.numpy.asarray(burst.positions, jax.numpy.int32),
        jax.numpy.asarray(burst.hi_positions, jax.numpy.int32),
        C_IPP, PAGES)
    r, n, cov, pinned = page_ref.sorted_workload_stats(plo, phi, PAGES)
    assert sp.total_refs == float(r)
    assert sp.distinct_pages == float(n)
    assert sp.pinned_retouches == float(pinned)
    assert sp.min_capacity == int(np.max(np.asarray(phi - plo + 1)))
    np.testing.assert_array_equal(np.asarray(sp.coverage), np.asarray(cov))


@pytest.mark.parametrize("n_windows", [255, 256, 257])
def test_bucketed_sorted_stream_with_junctions(n_windows):
    """Windows that share boundary pages: the pinned re-touch count stops
    at the last real window, whatever the padding holds."""
    lo = np.arange(n_windows) * 100
    hi = lo + 150                        # overlaps the next window's page
    wl = Workload.sorted_stream(lo, hi, n=N)
    sp = sorted_part_for(wl, 0, GEOM, PAGES)
    plo, phi = page_ref.page_intervals(
        jax.numpy.asarray(lo, jax.numpy.int32),
        jax.numpy.asarray(hi, jax.numpy.int32), C_IPP, PAGES)
    r, n, cov, pinned = page_ref.sorted_workload_stats(plo, phi, PAGES)
    assert float(pinned) > 0
    assert (sp.total_refs, sp.distinct_pages, sp.pinned_retouches) == (
        float(r), float(n), float(pinned))
    np.testing.assert_array_equal(np.asarray(sp.coverage), np.asarray(cov))


def test_lanes_and_pad_lanes_add_up_to_the_bucket(tmp_path):
    obs.clear()
    wl = _batch(300, 0)
    cost = CostSession(System(GEOM, memory_budget_bytes=64 * 1024))
    cands = [GridCandidate(knob=8, eps=8, size_bytes=1024.0)]
    with jax.profiler.trace(str(tmp_path)):
        cost.grid_profiles(cands, wl, executor="host")
        sorted_part_for(merge_burst_workload(np.arange(0, N, 300), N, C_IPP),
                        0, GEOM, PAGES)
    counts = obs.snapshot()["counts"]
    obs.clear()
    lanes = [c[2] for c in counts if c[0] == "profile.lanes"]
    pads = [c[2] for c in counts if c[0] == "profile.pad_lanes"]
    assert lanes == [300, 200]
    assert [a + b for a, b in zip(lanes, pads)] == [512, 256]


def _ycsb_like(keys, batches, batch_size, seed):
    """50/50 reads and updates on zipf-skewed keys scattered over the file."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(batches):
        ranks = np.minimum(rng.zipf(1.2, batch_size) - 1, keys.shape[0] - 1)
        pos = (ranks * 2654435761) % keys.shape[0]
        reads = rng.random(batch_size) < 0.5
        out.append([TraceEvent("point" if r else "update", key=float(k))
                    for r, k in zip(reads, keys[pos])])
    return out


def test_warm_write_session_compiles_nothing():
    keys = np.sort(np.random.default_rng(3).uniform(0, 1e9, N))
    system = System(GEOM, memory_budget_bytes=48 * 1024, policy="lfu")
    cand = GridCandidate(knob=32, eps=32, size_bytes=2048.0)
    session = WriteSession(
        keys, system, CamMergeScheduler(), candidate=cand,
        config=WriteConfig(batch_size=600, delta_capacity_entries=1_500,
                           profile_executor="host", price_executor="host"))
    batches = _ycsb_like(keys, 24, 600, seed=11)
    compiles = []

    def listener(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    warm = session.run([e for b in batches[:12] for e in b])
    assert warm.merges >= 1             # the burst met its buckets
    seen = len(compiles)
    report = session.run([e for b in batches[12:] for e in b])
    assert len(compiles) == seen, "a warm write batch compiled"
    assert report.merges >= 1 and report.decision_events == 12
