"""Entry ``serve``: ``ServingSession.observe``, one batch per call.

Set-up builds the key set, the traffic pool and the session (one RMI per
branch knob), fills the sketch window and deploys the initial tune, then
drives the rest of the pool once so that every shape the window meets is
compiled.  The window cycles through the pool, closed loop.

After the window, the plain reference (``reference.py``) re-derives what
the timed path produced and :meth:`ServeEntry.check` compares:

* ``locate_mismatches``: positions of a seeded sample of batches;
* ``profile_gap``: their per-candidate page histograms, request mass and
  E[DAC] (largest relative gap);
* ``tv_gap``: every window batch's drift distance;
* ``hit_gap``: every cell's hit rate in a seeded sample of retunes;
* ``choice_mismatches``: the chosen (branch, capacity) of those retunes;
* ``switch_mismatches``: their rebuild decision.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import reference
import traffic

#: Seeded sample sizes of the comparison.
PROFILE_SAMPLE = 8
RETUNE_SAMPLE = 8
#: Decisions whose two best options lie within this relative distance are
#: ties under float32 rounding and are not counted as mismatches.
TIE_REL = 1e-5

#: Each limit lies between the program's largest reading over its seeds
#: and the control's smallest (PERF.md, "How correct is decided").  The
#: counts and ``tv_gap`` are exact comparisons.
LIMITS = {
    "locate_mismatches": 0,
    "profile_gap": 1e-5,
    "tv_gap": 0.0,
    "hit_gap": 5e-6,
    "choice_mismatches": 0,
    "switch_mismatches": 0,
}


class ServeEntry:
    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config = config
        self.mix = mix
        self.seed = int(seed)
        self.spans = spans
        self.seq = 0                  # batches fed to the session so far
        self.deploys: List[int] = []  # seqs after which a config deployed
        self.records: Dict[int, dict] = {}
        self.retunes: List[dict] = []
        self.profiled: List[dict] = []   # reservoir of sampled batches
        self._reservoir_rng = np.random.default_rng([self.seed % 2**64, 11])
        self._window_seen = 0
        self._last_workload = None
        self._in_window = False
        self._deployment = None

    # ----------------------------------------------------------------- setup
    def setup(self, phase) -> None:
        from repro.core.cam import CamGeometry
        from repro.core.session import System
        from repro.serving import ServingConfig, ServingSession
        from repro.serving import session as serving_mod
        from repro.serving.trace import TraceEvent
        from repro.tuning.session import RMIBuilder, TuningSession

        cfg = self.config
        with phase("keys"):
            self.keys = traffic.make_keys(cfg["key_set"], cfg["keys"],
                                          cfg["key_seed"])
        with phase("traffic"):
            pool = traffic.make_pool(self.mix, self.keys, self.seed)
            bs = cfg["serving"]["batch_size"]
            self.batch_keys = [qk[a:a + bs] for qk in pool["point"]
                               for a in range(0, qk.shape[0], bs)]
            ts = iter(range(sum(b.shape[0] for b in self.batch_keys)))
            self.batches = [[TraceEvent("point", key=k, ts=float(next(ts)))
                             for k in b.tolist()] for b in self.batch_keys]
        with phase("session"):
            system = System(CamGeometry(c_ipp=cfg["c_ipp"],
                                        page_bytes=cfg["page_bytes"]),
                            memory_budget_bytes=cfg["budget_bytes"],
                            policy=cfg["policy"])
            tuning = TuningSession(system, splits=tuple(cfg["splits"]))
            self.session = ServingSession(
                tuning, RMIBuilder(self.keys), self.keys,
                overrides={"branch": tuple(cfg["index"]["branch_grid"])},
                config=ServingConfig(**cfg["serving"]))
        self._install(serving_mod, tuning.cost.engine)
        w = cfg["serving"]["window_chunks"]
        if len(self.batches) < 2 * w:
            raise ValueError("the traffic pool must hold two sketch windows")
        with phase("warmup"):
            start = [e for b in self.batches[:w] for e in b]
            self.seq = w
            result = self.session.start(start)
            self.deploys.append(w - 1)
            self.current = (result.best_knob, result.capacity_pages)
            # every further pool batch once: each shape compiles here
            for _ in range(w, len(self.batches)):
                self.call()

    def _install(self, serving_mod, engine) -> None:
        """Keep what the comparison needs; in a traced run also time it."""
        compile_events = serving_mod.compile_events
        self._restore = lambda: setattr(serving_mod, "compile_events",
                                        compile_events)

        def locate(*args, **kwargs):
            self._last_workload = compile_events(*args, **kwargs)
            return self._last_workload

        price = engine.price

        def priced(table, *args, **kwargs):
            sol = price(table, *args, **kwargs)
            knob_of = {}
            for kn, (a, b) in table.spans.items():
                for t in range(a, b):
                    knob_of[t] = kn
            self.records.setdefault(self.seq - 1, {})["price"] = {
                "cells": [(knob_of[t], int(c))
                          for t, c in enumerate(table.caps)],
                "hit": np.asarray(sol.hit_rates, np.float64).copy()}
            if self._in_window:
                self.spans.work["price"].append({
                    "rows": int(np.unique(table.rows).shape[0]),
                    "pages": int(table.profiles.counts.shape[1]),
                    "cells": len(table), "write_rows": 0})
            return sol

        sketch = self.session.sketch
        update = sketch.update

        def profiled(workload):
            chunk = update(workload)
            if self._in_window:
                self.spans.work["profile"].append({
                    "queries": int(workload.n_queries),
                    "rows": int(chunk.counts.shape[0]),
                    "pages": int(chunk.counts.shape[1])})
            return chunk

        if self.spans.annotate:
            locate_fn = self.spans.wrap("locate", locate)
            priced_fn = self.spans.wrap("price", priced)
            profiled_fn = self.spans.wrap("profile", profiled)
        else:
            locate_fn, priced_fn, profiled_fn = locate, priced, profiled
        serving_mod.compile_events = locate_fn
        engine.price = priced_fn
        sketch.update = profiled_fn

    # ---------------------------------------------------------------- window
    def begin_window(self) -> None:
        self._in_window = True

    def call(self) -> int:
        """One batch through ``ServingSession.observe``; returns its ops."""
        i = self.seq % len(self.batches)
        self.seq += 1
        (report,) = self.session.observe(self.batches[i])
        rec = self.records.setdefault(self.seq - 1, {})
        rec["tv"] = report.tv
        d = report.decision
        if d is not None:
            rec["decision"] = {
                "current": self.current, "switched": bool(d.switched),
                "best": (d.result.best_knob, d.result.capacity_pages)}
            if d.switched:
                self.deploys.append(self.seq - 1)
                self.current = (d.result.best_knob, d.result.capacity_pages)
        if self._in_window:
            rec["window"] = True
            if d is not None:
                self.retunes.append(self.seq - 1)
            self._sample_profile(self.seq - 1, i)
        return report.n_queries

    def _sample_profile(self, seq: int, pool_index: int) -> None:
        """Seeded reservoir: a uniform sample of the window's batches."""
        self._window_seen += 1
        item = {"seq": seq, "pool": pool_index,
                "positions": np.asarray(self._last_workload.positions),
                "chunk": self.session.sketch.chunks[-1]}
        if len(self.profiled) < PROFILE_SAMPLE:
            self.profiled.append(item)
        else:
            j = int(self._reservoir_rng.integers(0, self._window_seen))
            if j < PROFILE_SAMPLE:
                self.profiled[j] = item

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self._restore()
        self.session = None

    # ----------------------------------------------------------------- check
    def reference(self, lut_round=None) -> "ServeReference":
        """The reference over this run's pool; ``lut_round`` makes the
        control.  The deployment (key set and RMIs) is built once."""
        if self._deployment is None:
            cfg = self.config
            self._deployment = reference.Deployment(
                self.keys, cfg["index"]["branch_grid"], c_ipp=cfg["c_ipp"],
                page_bytes=cfg["page_bytes"],
                budget_bytes=cfg["budget_bytes"], splits=cfg["splits"])
        return ServeReference(self._deployment, self.batch_keys, lut_round)

    def check(self, ref: "ServeReference", program=None) -> Dict[str, float]:
        """The compared numbers.  ``program`` stands in for the recorded
        outputs (the control passes another reference here)."""
        cfg = self.config
        w = cfg["serving"]["window_chunks"]
        n_pool = len(self.batches)
        out = {}
        # locate and occupancy of the sampled batches
        mism, gap = 0, 0.0
        for item in self.profiled:
            prof = ref.profile(item["pool"])
            if program is not None:
                got = program.profile(item["pool"])
                pos, counts, dacs = got.positions, got.counts, got.dacs
                totals = got.counts.sum(axis=1)
            else:
                ch = item["chunk"]
                pos, counts = item["positions"], ch.counts
                totals = ch.totals
                dacs = ch.dac_mass / max(ch.n_queries, 1)
            if pos.shape != prof.positions.shape:
                mism += max(pos.shape[0], prof.positions.shape[0])
                gap = 1.0           # another batch: no histogram compares
                continue
            mism += int(np.sum(pos != prof.positions))
            want = prof.counts.sum(axis=1)
            gap = max(gap,
                      float(np.max(np.abs(counts - prof.counts).sum(axis=1)
                                   / np.maximum(want, 1e-300))),
                      float(np.max(np.abs(totals - want) / want)),
                      float(np.max(np.abs(dacs - prof.dacs) / prof.dacs)))
        out["locate_mismatches"] = mism
        out["profile_gap"] = gap

        # drift distance of every window batch, against the window deployed
        def pops(seq, src):
            return sum(src.page_pop((s % n_pool)) for s in
                       range(seq - w + 1, seq + 1))
        tv_gap = 0.0
        for seq, rec in sorted(self.records.items()):
            if not rec.get("window"):
                continue
            base = max(d for d in self.deploys if d < seq)
            want = reference.tv_distance(pops(seq, ref), pops(base, ref))
            got = (rec["tv"] if program is None else reference.tv_distance(
                pops(seq, program), pops(base, program)))
            tv_gap = max(tv_gap, abs(got - want))
        out["tv_gap"] = tv_gap

        # pricing and decisions of the sampled retunes
        rng = np.random.default_rng([self.seed % 2**64, 13])
        picks = (sorted(rng.choice(self.retunes, size=RETUNE_SAMPLE,
                                   replace=False).tolist())
                 if len(self.retunes) > RETUNE_SAMPLE else self.retunes)
        hit_gap, choice, switch = 0.0, 0, 0
        for seq in picks:
            rec = self.records[seq]
            window = [(s % n_pool) for s in range(seq - w + 1, seq + 1)]
            want = ref.price(window)
            cells = [(b, c) for _, b, c in want.cells]
            if program is None:
                got_cells = rec["price"]["cells"]
                got_hit = rec["price"]["hit"]
                best = rec["decision"]["best"]
                switched = rec["decision"]["switched"]
            else:
                got = program.price(window)
                got_cells, got_hit = cells, got.hit
                j = int(np.argmin(got.io))
                best = cells[j]
                switched = ref.switches(got, rec["decision"]["current"],
                                        cfg, best)
            if list(map(tuple, got_cells)) != cells:
                hit_gap = 1.0       # a different table: no cell compares
                continue
            hit_gap = max(hit_gap, float(np.max(np.abs(got_hit - want.hit))))
            order = np.argsort(want.io, kind="stable")
            j0, j1 = int(order[0]), int(order[1])
            tie = want.io[j1] - want.io[j0] <= TIE_REL * want.io[j0]
            if tuple(best) != cells[j0] and not tie:
                choice += 1
            rule = ref.switches(want, rec["decision"]["current"], cfg,
                                cells[j0], margin=TIE_REL)
            if rule is not None and rule != switched and not tie:
                switch += 1
        out["hit_gap"] = hit_gap
        out["choice_mismatches"] = choice
        out["switch_mismatches"] = switch
        out["_sampled"] = {"batches": len(self.profiled),
                           "retunes": len(picks),
                           "window_retunes": len(self.retunes)}
        return out


class ServeReference:
    """Reference profiles of the pool's batches, priced on demand."""

    def __init__(self, dep: reference.Deployment, batch_keys, lut_round):
        self.dep = dep
        self.batch_keys = batch_keys
        self.lut_round = lut_round
        self._profiles: Dict[int, reference.BatchProfile] = {}
        self._pops: Dict[int, np.ndarray] = {}

    def page_pop(self, i: int) -> np.ndarray:
        if i not in self._pops:
            self._pops[i] = reference.page_popularity(
                reference.locate(self.dep.keys, self.batch_keys[i]),
                self.dep.c_ipp, self.dep.pages)
        return self._pops[i]

    def profile(self, i: int) -> reference.BatchProfile:
        if i not in self._profiles:
            self._profiles[i] = self.dep.profile(self.batch_keys[i],
                                                 self.lut_round)
        return self._profiles[i]

    def price(self, window) -> reference.Priced:
        return reference.price_window(self.dep,
                                      [self.profile(i) for i in window])

    def switches(self, priced: reference.Priced, current, cfg, best,
                 margin: float = 0.0):
        """The rebuild rule on reference prices: switch iff the knob
        changes and the steady-state savings over the horizon repay the
        key-file scan, the index write and the buffer refill.  ``None``
        where savings and cost lie within ``margin`` of each other."""
        knob, cap = current
        cells = priced.cells
        mine = [(abs(c - cap), t) for t, (_, b, c) in enumerate(cells)
                if b == knob]
        if not mine:
            return True
        io_cur = priced.io[min(mine)[1]]
        j = [t for t, (_, b, c) in enumerate(cells) if (b, c) == tuple(best)]
        io_new = priced.io[j[0]]
        row = cells[j[0]][0]
        rebuild = (self.dep.pages
                   + math.ceil(self.dep.sizes[row] / self.dep.page_bytes)
                   + min(float(best[1]), priced.distinct[row]))
        savings = (io_cur - io_new) * cfg["serving"]["horizon_queries"]
        if abs(savings - rebuild) <= margin * max(abs(savings), rebuild):
            return None
        return bool(best[0] != knob and savings > rebuild)
