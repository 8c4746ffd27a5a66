"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default scale is CPU-sized (~100x
below paper scale, regime-preserving); see benchmarks/common.py.
``--smoke`` shrinks every benchmark that exposes a size knob another ~10x for
CI (fast, still exercising the full code paths).
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time

_SMOKE_KWARGS = {
    "n": 200_000,
    "n_queries": 20_000,
    "n_outer": 5_000,
    "n_pages": 50_000,
    "smoke": True,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark names")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized inputs (~10x below the CPU default)")
    args = ap.parse_args()

    from benchmarks import (bench_covariance, bench_engine,
                            bench_estimate_grid, bench_fetch_strategy,
                            bench_io_size, bench_join, bench_kv_planner,
                            bench_pgm_tuning_curve,
                            bench_point_accuracy, bench_profile_grid,
                            bench_range_accuracy, bench_rmi_tuning_curve,
                            bench_serving_drift, bench_sharding,
                            bench_tuning_e2e, bench_write_path)

    table = {
        "point_accuracy": bench_point_accuracy.run,     # Table IV / Fig 1
        "range_accuracy": bench_range_accuracy.run,     # Table V
        "io_size": bench_io_size.run,                   # Table I
        "covariance": bench_covariance.run,             # Table II
        "fetch_strategy": bench_fetch_strategy.run,     # Fig 5 + Lemmas
        "pgm_tuning_curve": bench_pgm_tuning_curve.run,  # Fig 7
        "rmi_tuning_curve": bench_rmi_tuning_curve.run,  # Fig 8
        "tuning_e2e": bench_tuning_e2e.run,             # Figs 9/10
        "join": bench_join.run,                         # Fig 11
        "kv_planner": bench_kv_planner.run,             # beyond-paper (Eq.15 serving)
        "estimate_grid": bench_estimate_grid.run,       # CostSession grid vs loop
        "serving_drift": bench_serving_drift.run,       # adaptive vs static
        "write_path": bench_write_path.run,             # CAM merge scheduler
        "sharding": bench_sharding.run,                 # solved vs even split
        "engine": bench_engine.run,                     # fused executor vs host
        "profile_grid": bench_profile_grid.run,         # device occupancy kernel
    }
    names = args.only or list(table)
    print("name,us_per_call,derived")
    for name in names:
        fn = table[name]
        kwargs = {}
        if args.smoke:
            params = inspect.signature(fn).parameters
            kwargs = {k: v for k, v in _SMOKE_KWARGS.items() if k in params}
        t0 = time.perf_counter()
        try:
            fn(**kwargs)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
