"""``correct`` comes out false for the control and for a broken timed path.

Each fault is planted in the program underneath a full CPU run of the
harness (which skips only the look for a chip); the control runs the
reference, with its Eq. 12 weights rounded to bfloat16, in the program's
place.
"""
import jax
import numpy as np

import control
import run


def run_tiny(tiny, seed=2**31 + 21):
    spec, cell, config, mix = tiny
    return run.run_cell(spec, cell, config, mix, seed=seed, seconds=2.0,
                        trace=False, devices=jax.devices())


def failing(res):
    return sorted(k for k, c in res["checks"].items()
                  if c["value"] > c["limit"])


def test_control_is_not_correct(tiny):
    _, _, config, mix = tiny
    for seed in (3, 2**31 + 5, 77):
        limits, prog, ctrl = control.readings(config, mix, seed, 1.0)
        assert all(prog[k] <= limits[k] for k in limits), prog
        assert any(ctrl[k] > limits[k] for k in limits), ctrl


def test_sketch_state_left_unchanged_is_caught(tiny, monkeypatch):
    from repro.serving.sketch import WindowSketch
    update = WindowSketch.update

    def stale(self, workload):
        if len(self.chunks) < self.window_chunks:
            return update(self, workload)
        return self.chunks[-1]          # the window never moves again

    monkeypatch.setattr(WindowSketch, "update", stale)
    res = run_tiny(tiny)
    assert not res["correct"]
    assert failing(res)


def test_half_the_batch_left_out_is_caught(tiny, monkeypatch):
    from repro.serving import session as serving_mod
    compile_events = serving_mod.compile_events
    monkeypatch.setattr(
        serving_mod, "compile_events",
        lambda events, keys: compile_events(events[:len(events) // 2], keys))
    res = run_tiny(tiny)
    assert not res["correct"]
    assert "locate_mismatches" in failing(res)


def test_altered_hit_rate_is_caught(tiny, monkeypatch):
    from repro.engine.host import HostExecutor
    solve = HostExecutor.solve

    def altered(self, engine, table, row_scale):
        h, nd, best = solve(self, engine, table, row_scale)
        return np.asarray(h) * (1 - 1e-3), nd, best

    monkeypatch.setattr(HostExecutor, "solve", altered)
    res = run_tiny(tiny)
    assert not res["correct"]
    assert "hit_gap" in failing(res)


def test_altered_histogram_is_caught(tiny, monkeypatch):
    from repro.core import page_ref
    from repro.core import session as core_session
    grid = page_ref.point_page_refs_mixed_eps_grid

    def altered(*args, **kwargs):
        counts, totals = grid(*args, **kwargs)
        counts = counts.copy()
        counts[:, 0] += 1.0             # one extra reference on page 0
        return counts, totals + 1.0

    monkeypatch.setattr(core_session.page_ref,
                        "point_page_refs_mixed_eps_grid", altered)
    res = run_tiny(tiny)
    assert not res["correct"]
    assert "profile_gap" in failing(res)
