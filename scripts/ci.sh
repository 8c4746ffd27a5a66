#!/usr/bin/env sh
# Tier-1 CI step — the single source of truth; .github/workflows/ci.yml
# invokes this script.
#
# Runs the whole test suite, then smokes the benchmarks covering the batched
# estimation paths (point/range grid kernels AND the policy-aware sorted
# grid), the tuning curve, the end-to-end tuner comparison (which records
# the mixed-eps-kernel speedup to benchmarks/results/tuning_e2e.json),
# the join planner (incl. the join-tree budget-split section), the
# serving drift loop (adaptive-vs-static gates recorded to
# benchmarks/results/serving_drift.json), the write-path merge scheduler
# (CAM-vs-baselines gates recorded to benchmarks/results/write_path.json),
# the sharded fleet search
# (solved-boundaries-vs-even-split gates recorded to
# benchmarks/results/sharding.json), and the pricing-engine executor pair
# (fused-kernel-vs-host equivalence/speed gates recorded to
# benchmarks/results/engine_fused.json), and the device occupancy-profiling
# kernel (host-vs-device mixed-eps equivalence/speed gates recorded to
# benchmarks/results/profile_grid.json), verifies that every results JSON the
# workflow uploads actually got written (catches silently-skipped smoke
# sections), and finally runs EVERY example script in --smoke mode so the
# README quickstarts stay executable.
#
# DeprecationWarning raised FROM repro.* code is an error: internal code
# must not call the deprecated tuner/estimator shims.  The gate lives in
# pyproject.toml's filterwarnings (module-regex entry, which a -W flag
# could not express — -W escapes and end-anchors the module field), so
# EVERY pytest invocation enforces it; tests exercising the shims directly
# attribute the warning to the test module and stay exempt.
set -e
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q
python -m benchmarks.run --smoke --only estimate_grid pgm_tuning_curve
python -m benchmarks.bench_tuning_e2e --smoke
python -m benchmarks.bench_join --smoke
python -m benchmarks.bench_serving_drift --smoke
python -m benchmarks.bench_write_path --smoke
python -m benchmarks.bench_sharding --smoke
python -m benchmarks.bench_engine --smoke
python -m benchmarks.bench_profile_grid --smoke

# every results JSON named in .github/workflows/ci.yml must exist after the
# bench step — a missing file means a smoke section silently skipped
for f in estimate_grid join_partition join_tree tuning_e2e serving_drift \
         write_path sharding engine_fused profile_grid; do
    if [ ! -f "benchmarks/results/$f.json" ]; then
        echo "MISSING benchmark result: benchmarks/results/$f.json" >&2
        exit 1
    fi
done

# every example must exit 0 at CI size (each accepts --smoke)
for ex in examples/*.py; do
    echo "== $ex --smoke"
    python "$ex" --smoke
done
