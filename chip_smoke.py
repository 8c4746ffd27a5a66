"""Chip smoke test: CAM's main path, "trace batch in, decision out", on one TPU.

Drives the three served paths through their public entry points at the
repo's scaled deployment (``configs/cam_paper.py`` ``SCALED_DEFAULT``:
2M SOSD-books keys, 4 KiB pages of 256 items, 200k w4 point queries), with
both Pallas kernels (``kernels/profile_grid.py`` occupancy,
``kernels/price_grid.py`` fused solve) compiled natively:

  A. tune  -- ``TuningSession.tune`` over RMI and PGM under a 2 MiB budget,
              with lru/fifo/lfu priced side by side in one launch;
  B. serve -- ``ServingSession`` over a drifting point trace (one regime
              change), RMI candidates, device occupancy profiling;
  C. write -- ``WriteSession`` over a read -> write-burst -> read trace
              (the ``examples/update_heavy.py`` deployment).

Each phase runs on the device executors twice (cold: compiles; warm) and
once on the host reference executors, in this one process, and fails
unless the two agree: identical winners and decisions, solved hit rates
within the ``tests/test_engine.py`` tolerance.  Phase A also replays the
tuned PGM configuration through the buffer (``core/replay.py``) and holds
the estimate's q-error under the ``tests/test_session.py`` bound.

Wall times printed along the way are one cold and one warm run each: set-up
timings, not metrics.  The last line is a JSON object naming the device.

    python chip_smoke.py [--seed N]

Exits non-zero without a TPU.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` next to this
file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.cam import CamGeometry  # noqa: E402
from repro.core.cache_models import POLICIES  # noqa: E402
from repro.core.qerror import q_error  # noqa: E402
from repro.core.replay import replay_windows  # noqa: E402
from repro.core.session import GridCandidate, System  # noqa: E402
from repro.core.workload import Workload  # noqa: E402
from repro.data.datasets import make_dataset  # noqa: E402
from repro.data.workloads import WorkloadSpec, point_workload  # noqa: E402
from repro.engine.device import DeviceExecutor  # noqa: E402
from repro.kernels import price_grid  # noqa: E402
from repro.serving import (ServingConfig, ServingSession,  # noqa: E402
                           synthetic_drifting_trace)
from repro.tuning.session import (PGMBuilder, RMIBuilder,  # noqa: E402
                                  TuningSession)
from repro.write import CamMergeScheduler, WriteConfig, WriteSession  # noqa: E402

HIT_TOL = 2e-6        # tests/test_engine.py: host vs device solved hit rates
QERR_BOUND = 1.4      # tests/test_session.py: estimator vs replay, point
# the forcing switch would override the executors each phase passes
ENV_EXECUTOR = "REPRO_ENGINE_EXECUTOR"


def log(**fields):
    print(json.dumps(fields), flush=True)


def record_solutions(engine):
    """Keep every PriceSolution the engine returns (for the hit-rate check)."""
    sols, price = [], engine.price

    def recorded(*args, **kwargs):
        sol = price(*args, **kwargs)
        sols.append(sol)
        return sol

    engine.price = recorded
    return sols


def check_solutions(phase, dev, host):
    assert len(dev) == len(host) > 0, (phase, len(dev), len(host))
    assert {s.executor for s in dev} == {"device"}, phase
    assert {s.executor for s in host} == {"host"}, phase
    diffs = [float(np.max(np.abs(d.hit_rates - h.hit_rates)))
             for d, h in zip(dev, host)]
    assert max(diffs) < HIT_TOL, (phase, diffs)
    return max(diffs)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_cold_warm_host(phase, run):
    """``run(device: bool) -> (result, solutions)``: cold + warm on the
    device executors, then the host reference on the CPU, where the test
    suite holds it (XLA's TPU ``expm1`` is off by up to 1e-4 relative,
    which moves LRU hit rates by ~7e-6)."""
    (dev, sols_d), cold = timed(lambda: run(True))
    (_, _), warm = timed(lambda: run(True))
    with jax.default_device(jax.devices("cpu")[0]):
        (host, sols_h), host_s = timed(lambda: run(False))
    dh = check_solutions(phase, sols_d, sols_h)
    log(phase=phase, setup_timing="one cold and one warm run, not a metric",
        cold_s=cold, warm_s=warm, host_reference_s=host_s,
        engine_calls=len(sols_d), max_hit_rate_diff=dh)
    return dev, host


# ---------------------------------------------------------------------------
# Phase A: tune
# ---------------------------------------------------------------------------

def same_tune(d, h, what):
    assert d.best == h.best and d.split == h.split, (what, d.best, d.split,
                                                     h.best, h.split)
    assert d.capacity_pages == h.capacity_pages, what
    assert list(d.table) == list(h.table), what


def phase_tune(keys, qk, qpos, system, device_executor):
    workload = Workload.point(qpos, n=len(keys), query_keys=qk)
    builders = {"rmi": RMIBuilder(keys), "pgm": PGMBuilder(keys)}

    def run(device):
        results, sols = {}, []
        for name, builder in builders.items():
            tuning = TuningSession(system)
            if device:
                tuning.cost.engine.executor = device_executor
            else:
                # pins profiling AND pricing to the golden host path
                os.environ[ENV_EXECUTOR] = "host"
            recorded = record_solutions(tuning.cost.engine)
            try:
                results[name] = tuning.tune(builder, workload,
                                            policies=POLICIES)
            finally:
                os.environ.pop(ENV_EXECUTOR, None)
            sols += recorded
        return results, sols

    dev, host = run_cold_warm_host("A_tune", run)
    for name in builders:
        same_tune(dev[name], host[name], name)
        log(phase="A_tune", family=name, best=dev[name].best,
            split=dev[name].split, capacity_pages=dev[name].capacity_pages,
            est_io=dev[name].est_io)

    # the tuned PGM configuration against buffered replay of the same trace
    res = dev["pgm"]
    adapter = builders["pgm"].build(res.best)
    lo, hi = adapter.window(qk)
    c_ipp = system.geom.c_ipp
    misses = replay_windows(lo // c_ipp, hi // c_ipp, res.capacity_pages,
                            res.best["policy"])
    qerr = float(q_error(res.est_io, misses.mean()))
    log(phase="A_tune", pgm_est_io=res.est_io,
        pgm_replay_io=float(misses.mean()), pgm_q_error=qerr,
        replayed_queries=int(misses.shape[0]))
    assert qerr < QERR_BOUND, qerr


# ---------------------------------------------------------------------------
# Phase B: serve
# ---------------------------------------------------------------------------

def phase_serve(keys, system, device_executor, batch, seed):
    events = synthetic_drifting_trace(keys, [
        {"events": 6 * batch, "mix": (1.0, 0.0, 0.0), "hot_center": 0.2,
         "hot_width": 0.05},
        {"events": 6 * batch, "mix": (1.0, 0.0, 0.0), "hot_center": 0.8,
         "hot_width": 0.05},
    ], seed=seed)
    builder = RMIBuilder(keys)

    def run(device):
        tuning = TuningSession(system)
        tuning.cost.engine.executor = device_executor if device else "host"
        sols = record_solutions(tuning.cost.engine)
        serving = ServingSession(
            tuning, builder, keys, overrides={"branch": (256, 1024, 4096)},
            config=ServingConfig(
                batch_size=batch, window_chunks=3, drift_threshold=0.12,
                hysteresis=0.04, cooldown_batches=1,
                horizon_queries=64 * batch,
                profile_executor="device" if device else "host"))
        initial = serving.start(events[:2 * batch])
        reports = serving.observe(events[2 * batch:])
        return (serving, initial, reports), sols

    (srv_d, init_d, rep_d), (srv_h, init_h, rep_h) = run_cold_warm_host(
        "B_serve", run)
    same_tune(init_d, init_h, "initial deploy")
    assert len(rep_d) == len(rep_h)
    for d, h in zip(rep_d, rep_h):
        assert d.drifted == h.drifted, (d.ts, d.tv, h.tv)
        assert (d.decision is None) == (h.decision is None), d.ts
        if d.decision is not None:
            dd, hd = d.decision, h.decision
            assert (dd.switched, dd.from_knob, dd.to_knob) == \
                (hd.switched, hd.from_knob, hd.to_knob), d.ts
            same_tune(dd.result, hd.result, f"retune at {d.ts}")
    assert srv_d.stats == srv_h.stats
    assert srv_d.stats.retune_evaluations > 0, "the trace should drift"
    s = srv_d.stats
    log(phase="B_serve", batches=s.batches, events=s.events,
        drift_events=s.drift_events, retunes=s.retune_evaluations,
        rebuilds=s.rebuilds, serving_branch=srv_d.current.best_knob)


# ---------------------------------------------------------------------------
# Phase C: write
# ---------------------------------------------------------------------------

def phase_write(device_executor, seed, scale=600, n=250_000):
    geom = CamGeometry(c_ipp=64, page_bytes=4096)
    keys = np.sort(np.random.default_rng(seed).uniform(0, 1e9, n))
    system = System(geom, memory_budget_bytes=160 * geom.page_bytes,
                    policy="lru")
    candidate = GridCandidate(knob="live", eps=64, size_bytes=4096.0)
    events = synthetic_drifting_trace(keys, [
        {"events": 8 * scale, "mix": (0.9, 0.05, 0.0, 0.05, 0.0, 0.0),
         "hot_center": 0.3, "hot_width": 0.08, "hot_frac": 0.95},
        {"events": 10 * scale, "mix": (0.2, 0.0, 0.0, 0.25, 0.5, 0.05),
         "hot_center": 0.7, "hot_width": 0.25, "hot_frac": 0.8},
        {"events": 16 * scale, "mix": (0.92, 0.05, 0.0, 0.01, 0.02, 0.0),
         "hot_center": 0.3, "hot_width": 0.08, "hot_frac": 0.95},
    ], seed=seed - 1)

    def run(device):
        config = WriteConfig(
            batch_size=scale, delta_capacity_entries=160 * scale,
            delta_entry_bytes=192.0, horizon_batches=12.0,
            profile_executor="device" if device else "host",
            price_executor=device_executor if device else "host")
        sess = WriteSession(keys, system, CamMergeScheduler(),
                            candidate=candidate, config=config)
        sols = record_solutions(sess.engine)
        return sess.run(events), sols

    dev, host = run_cold_warm_host("C_write", run)
    assert [(r.merged, r.reason) for r in dev.records] == \
        [(r.merged, r.reason) for r in host.records]
    assert dev.merges > 0, "the write burst should trigger a merge"
    log(phase="C_write", batches=len(dev.records), merges=dev.merges,
        engine_calls=dev.engine_calls, total_io=dev.total_io,
        host_total_io=host.total_io)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1,
                    help="seed of every generated key set and trace")
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{jax.default_backend()!r}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.pop(ENV_EXECUTOR, None)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(device=device, price_grid_max_pages_v5e=price_grid.V5E_MAX_PAGES,
        profile_grid_max_pages_v5e="no VMEM bound (fixed page tiles)")

    # pricing is pinned native here; profiling (sessions name only an
    # executor) compiles natively whenever the backend is a TPU, as above
    device_executor = DeviceExecutor(interpret=False)
    keys = make_dataset("books", 2_000_000, seed=args.seed)
    qk, qpos = point_workload(keys, 200_000, WorkloadSpec("w4", seed=3))
    system = System(CamGeometry(c_ipp=256, page_bytes=4096),
                    memory_budget_bytes=2 << 20, policy="lru")
    log(keys=len(keys), pages=system.geom.num_pages(len(keys)),
        queries=len(qk), budget_bytes=system.memory_budget_bytes)

    phase_tune(keys, qk, qpos, system, device_executor)
    phase_serve(keys, system, device_executor, batch=10_000, seed=args.seed)
    phase_write(device_executor, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
