"""CPU tests of the benchmark harness (not part of the repository's tier-1
suite): ``python -m pytest bench/tests`` from the repository root, with
``JAX_PLATFORMS=cpu``."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


@pytest.fixture
def tiny():
    """The serve cell at a size a CPU test holds: 200k keys, a buffer of
    32 pages, batches of 1,000 events, three regimes of eight batches."""
    import run
    config = run.load_json(run.HERE, "configs", "books_rmi.json")
    mix = run.load_json(run.HERE, "traffic", "w4_drift.json")
    config.update(keys=200_000, budget_bytes=134_217)
    config["serving"] = dict(config["serving"], batch_size=1_000)
    mix.update(regime_events=8_000, regimes=3)
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(c for c in spec["workloads"]
                if c["name"] == "books_rmi.w4_drift")
    return spec, cell, config, mix
