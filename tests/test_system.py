"""End-to-end behaviour tests for the whole system.

Covers: the full CAM pipeline (dataset -> index -> workload -> estimate vs
replay), memory-budget tuning end-to-end, and the join pipeline.
"""
import numpy as np
import pytest

from repro.core import cam
from repro.core.qerror import q_error
from repro.core.replay import replay_windows
from repro.data.datasets import make_dataset
from repro.data.workloads import WorkloadSpec, point_workload, join_outer_keys
from repro.index.disk_layout import PageLayout
from repro.index.pgm import build_pgm
from repro.join.executors import hybrid_join, inlj
from repro.core.session import System
from repro.core.workload import Workload
from repro.tuning.session import PGMBuilder, TuningSession

GEOM = cam.CamGeometry()
LAYOUT = PageLayout()


@pytest.fixture(scope="module")
def world():
    keys = make_dataset("books", 500_000, seed=1)
    qk, qpos = point_workload(keys, 60_000, WorkloadSpec("w4", seed=3))
    return keys, qk, qpos


def test_cam_end_to_end_accuracy(world):
    """The headline claim: CAM matches replay (Q-error ~1.0x) replay-free."""
    keys, qk, qpos = world
    for eps in (16, 64, 256):
        idx = build_pgm(keys, eps)
        budget = 3 << 20
        est = cam.estimate_point_io(qpos, eps, len(keys), GEOM, budget,
                                    idx.size_bytes, policy="lru")
        cap = max(1, (budget - idx.size_bytes) // GEOM.page_bytes)
        wlo, whi = idx.window(qk)
        misses = replay_windows(wlo // GEOM.c_ipp, whi // GEOM.c_ipp,
                                cap, "lru")
        assert float(q_error(est.io_per_query, misses.mean())) < 1.25, eps


def test_cam_tuning_end_to_end(world):
    """CAM-chosen eps must be within 15% of the oracle-best actual I/O."""
    keys, qk, qpos = world
    budget = int(1.2 * 2**20)
    grid = (8, 16, 32, 64, 128, 256, 512)
    res = TuningSession(System(GEOM, budget, "lru")).tune(
        PGMBuilder(keys), Workload.point(qpos, n=len(keys)),
        overrides={"eps": grid})
    actual = {}
    for eps in grid:
        idx = build_pgm(keys, eps)
        if idx.size_bytes >= budget - GEOM.page_bytes:
            continue
        cap = max(1, (budget - idx.size_bytes) // GEOM.page_bytes)
        wlo, whi = idx.window(qk)
        actual[eps] = replay_windows(wlo // GEOM.c_ipp, whi // GEOM.c_ipp,
                                     cap, "lru").mean()
    best_actual = min(actual.values())
    assert actual[res.best_knob] <= 1.15 * best_actual


def test_join_end_to_end(world):
    keys, _, _ = world
    idx = build_pgm(keys, 64)
    outer = join_outer_keys(keys, 30_000, WorkloadSpec("w3", seed=7))
    cap = (1 << 20) // LAYOUT.page_bytes
    st_inlj = inlj(idx, keys, outer, LAYOUT, cap)
    st_h = hybrid_join(idx, keys, outer, LAYOUT, cap, n_min=256)
    assert st_h.matches == st_inlj.matches == int(np.isin(outer, keys).sum())
    assert st_h.seconds < st_inlj.seconds     # hotspot workload: big win
