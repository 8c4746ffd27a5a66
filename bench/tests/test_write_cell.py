"""The write cell, ``osm_pgm.ycsb_a``, end to end on the CPU: a tiny run is
``correct``, the control is not, and planted faults in the write path are
caught.

Each fault is planted in the program underneath a full CPU run of the
harness (which skips only the look for a chip); the control runs the
reference, with its Eq. 12 weights rounded to bfloat16, in the program's
place.
"""
import jax
import numpy as np
import pytest

import control
import run

CELL = "osm_pgm.ycsb_a"


@pytest.fixture
def tiny_write():
    """The write cell at a size a CPU test holds: 200k keys, a buffer of
    32 pages, a delta of 4,192 entries (half of it), batches of 1,000
    operations, a pool of 24 batches."""
    config = run.load_json(run.HERE, "configs", "osm_pgm.json")
    mix = run.load_json(run.HERE, "traffic", "ycsb_a.json")
    config.update(keys=200_000, budget_bytes=134_217)
    config["write"] = dict(config["write"], batch_size=1_000,
                           delta_capacity_entries=4_192)
    mix.update(batch_events=1_000, batches=24)
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(c for c in spec["workloads"] if c["name"] == CELL)
    return spec, cell, config, mix


def run_tiny(tiny_write, seed=2**31 + 21, trace=False):
    spec, cell, config, mix = tiny_write
    return run.run_cell(spec, cell, config, mix, seed=seed, seconds=2.0,
                        trace=trace, devices=jax.devices())


def failing(res):
    return sorted(k for k, c in res["checks"].items()
                  if c["value"] > c["limit"])


def test_tiny_write_run_is_correct(tiny_write, capsys):
    res = run_tiny(tiny_write)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ops_per_s", "batch_ms_p50",
                                   "batch_ms_p95", "setup_s"}
    err = capsys.readouterr().err
    assert '"window_compiles": 0' in err
    assert '"window_retunes": 0' not in err      # the window merged


def test_tiny_traced_write_run_reads_nothing_off_the_chip(tiny_write):
    res = run_tiny(tiny_write, trace=True)
    assert res["correct"], res["checks"]
    # the cell's readers read the program's spans on the chip only
    assert res["metrics"] == {}
    assert res["device"]["window_s"] > 0


def test_write_control_is_not_correct(tiny_write):
    _, _, config, mix = tiny_write
    for seed in (3, 2**31 + 5, 77):
        limits, prog, ctrl = control.readings(config, mix, seed, 1.0)
        assert all(prog[k] <= limits[k] for k in limits), prog
        assert any(ctrl[k] > limits[k] for k in limits), ctrl


def test_an_update_dropped_from_the_delta_is_caught(tiny_write, monkeypatch):
    from repro.core.workload import UPDATE, Workload
    from repro.write.delta import DeltaBuffer
    stage = DeltaBuffer.stage

    def dropped(self, workload):
        if workload.kind == UPDATE and workload.n_queries > 1:
            workload = Workload.update(workload.positions[1:],
                                       n=workload.n)
        return stage(self, workload)

    monkeypatch.setattr(DeltaBuffer, "stage", dropped)
    res = run_tiny(tiny_write)
    assert not res["correct"]
    assert "capacity_mismatches" in failing(res)


def test_a_stale_burst_is_caught(tiny_write, monkeypatch):
    from repro.write import session as write_mod
    burst = write_mod.merge_burst_workload
    last = []

    def stale(*args):
        fresh = burst(*args)
        out = last[0] if last else fresh     # one batch behind
        last[:] = [fresh]
        return out

    monkeypatch.setattr(write_mod, "merge_burst_workload", stale)
    res = run_tiny(tiny_write)
    assert not res["correct"]
    assert "burst_mismatches" in failing(res)


def test_an_altered_hit_rate_is_caught(tiny_write, monkeypatch):
    from repro.engine.host import HostExecutor
    solve = HostExecutor.solve

    def altered(self, engine, table, row_scale):
        h, nd, best = solve(self, engine, table, row_scale)
        return np.asarray(h) * (1 - 1e-3), nd, best

    monkeypatch.setattr(HostExecutor, "solve", altered)
    res = run_tiny(tiny_write)
    assert not res["correct"]
    assert "io_gap" in failing(res)


def test_a_flipped_merge_decision_is_caught(tiny_write, monkeypatch):
    from repro.write.scheduler import CamMergeScheduler, MergeDecision
    decide = CamMergeScheduler.decide

    def flipped(self, ctx):
        d = decide(self, ctx)
        if d.reason != "priced":
            return d
        return MergeDecision(not d.merge, d.reason, d.benefit, d.cost)

    monkeypatch.setattr(CamMergeScheduler, "decide", flipped)
    res = run_tiny(tiny_write)
    assert not res["correct"]
    assert "decision_mismatches" in failing(res)


def _late(decide):
    """A full delta defers once, then merges a batch late."""
    waited = []

    def late(self, ctx):
        d = decide(self, ctx)
        if d.reason == "full" and not waited:
            waited.append(ctx.batch_index)
            return type(d)(False, "late")
        waited.clear()
        return d
    return late


def _early(decide):
    """A delta that is not full merges two batches after the last merge,
    where the priced rule defers."""
    def early(self, ctx):
        d = decide(self, ctx)
        if d.reason == "priced" and not d.merge \
                and ctx.batches_since_merge == 2:
            return type(d)(True, "early", d.benefit, d.cost)
        return d
    return early


@pytest.mark.parametrize("fault", [_late, _early], ids=["late", "early"])
def test_a_merge_off_the_rule_at_any_batch_is_caught(tiny_write, monkeypatch,
                                                     fault):
    """Caught with no sampled batch at all: the late merge by the rule
    checked at every batch, the early one by that and by its merge batch's
    re-derived pricing."""
    from repro.write.scheduler import CamMergeScheduler
    monkeypatch.setattr(CamMergeScheduler, "decide",
                        fault(CamMergeScheduler.decide))
    load = run.load_module

    def unsampled(kind, name):
        mod = load(kind, name)
        if kind == "entries":
            mod.EVENT_SAMPLE = 0
        return mod

    monkeypatch.setattr(run, "load_module", unsampled)
    res = run_tiny(tiny_write)
    assert not res["correct"]
    assert "decision_mismatches" in failing(res)
