"""Entry ``write``: ``WriteSession.run``, one batch per call.

Set-up builds the osm key set, the YCSB pool, the PGM-index and the session,
then drives the whole pool once: the delta fills and merges at least once,
so every lane bucket of the read profile and of the merge burst compiles
there.  A program that keeps compiling stops set-up with an error: a run
of ``RECOMPILE_STREAK`` warm-up batches that each compiled, or any compile
in ``GUARD_BATCHES`` further pool batches after the warm-up.  The window
cycles through the pool, closed loop.

After the window, the plain reference (``reference_write.py``) re-derives
what the timed path produced and :meth:`WriteEntry.check` compares:

* ``locate_mismatches``: the read ranks of a seeded sample of window
  batches;
* ``profile_gap``: their read histograms, request mass and E[DAC] (largest
  relative gap);
* ``burst_mismatches``: their merge bursts' sorted windows, coverage and
  statistics (integers);
* ``io_gap``: their decision events' ``io_defer``, ``io_merged`` and burst
  I/O (largest relative gap);
* ``decision_mismatches``: merge or defer at those events, and at every
  window batch the merge rule applied to the delta's re-derived size and
  the program's own priced I/O;
* ``capacity_mismatches``: the delta's entries and the buffer's capacity
  at every window batch.

Every window batch that merged joins the sample, so each merge's pricing
is re-derived too.  The delta's history follows the merges the program
made, so one wrong decision counts once and does not spread to every
later batch.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import reference_write
import ycsb

#: Seeded sample of window batches the reference re-derives.
EVENT_SAMPLE = 8
#: Decisions whose benefit and cost lie within this relative distance are
#: ties under float32 rounding and are not counted as mismatches.
TIE_REL = 1e-5
#: Set-up fails once this many warm-up batches in a row compiled.
RECOMPILE_STREAK = 8
#: Pool batches after the warm-up that must compile nothing.
GUARD_BATCHES = 4

#: Each limit lies between the program's largest reading over its seeds
#: and the control's smallest (PERF.md, "How correct is decided").  The
#: counts are exact comparisons.
LIMITS = {
    "locate_mismatches": 0,
    "profile_gap": 1e-5,
    "burst_mismatches": 0,
    "io_gap": 1e-6,
    "decision_mismatches": 0,
    "capacity_mismatches": 0,
}


class WriteEntry:
    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config = config
        self.mix = mix
        self.seed = int(seed)
        self.spans = spans
        self.seq = 0                      # batches fed to the session so far
        self.ledger: List[dict] = []      # one row per batch, every batch
        self.events: List[dict] = []      # reservoir of sampled batches
        self.merge_events: List[dict] = []  # every window batch that merged
        self.retunes: List[int] = []      # merges inside the window
        self._reservoir_rng = np.random.default_rng([self.seed % 2**64, 17])
        self._window_seen = 0
        self._in_window = False
        self._last: Dict[str, object] = {}
        self._compiles = 0
        self._deployment = None

    # ----------------------------------------------------------------- setup
    def setup(self, phase) -> None:
        import jax.monitoring

        from repro.core.cam import CamGeometry
        from repro.core.session import GridCandidate, System
        from repro.index.pgm import build_pgm
        from repro.serving.trace import TraceEvent
        from repro.write import CamMergeScheduler, WriteConfig, WriteSession
        from repro.write import session as write_mod

        cfg = self.config
        with phase("keys"):
            self.keys = ycsb.osm_keys(cfg["keys"], cfg["key_seed"])
        with phase("traffic"):
            self.pool = ycsb.make_pool(self.mix, self.keys.shape[0],
                                       self.seed)
            ts = 0
            self.batches = []
            for b in self.pool:
                keys = self.keys[b.positions].tolist()
                self.batches.append([
                    TraceEvent("point" if r else "update", key=k,
                               ts=float(ts + j))
                    for j, (r, k) in enumerate(zip(b.reads.tolist(), keys))])
                ts += len(keys)
        with phase("session"):
            eps = int(cfg["index"]["eps"])
            pgm = build_pgm(self.keys, eps)
            system = System(CamGeometry(c_ipp=cfg["c_ipp"],
                                        page_bytes=cfg["page_bytes"]),
                            memory_budget_bytes=cfg["budget_bytes"],
                            policy=cfg["policy"])
            sched = dict(cfg["scheduler"])
            sched.pop("name")
            self.session = WriteSession(
                self.keys, system, CamMergeScheduler(**sched),
                candidate=GridCandidate(knob=eps, eps=eps,
                                        size_bytes=pgm.size_bytes),
                config=WriteConfig(**cfg["write"]))
        self._install(write_mod)

        def count(event, duration, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1
        jax.monitoring.register_event_duration_secs_listener(count)
        with phase("warmup"):
            streak = 0
            for _ in range(len(self.batches)):
                before = self._compiles
                self.call()
                streak = streak + 1 if self._compiles > before else 0
                if streak >= RECOMPILE_STREAK:
                    raise RuntimeError(
                        f"the write path compiled in each of {streak} "
                        "batches in a row: it recompiles every batch")
            before = self._compiles
            for _ in range(GUARD_BATCHES):
                self.call()
            if self._compiles > before:
                raise RuntimeError(
                    f"{self._compiles - before} compiles in {GUARD_BATCHES} "
                    "batches after the warm-up: the write path recompiles "
                    "per batch")
            if not any(row["merged"] for row in self.ledger):
                raise RuntimeError("the warm-up never merged the delta: "
                                   "the burst's shapes are not all warm")

    def _install(self, write_mod) -> None:
        """Keep what the comparison needs; in a traced run also time it."""
        session = self.session
        compile_events = write_mod.compile_events
        merge_burst = write_mod.merge_burst_workload
        burst_profiles = session._burst_profiles
        self._restore = lambda: (
            setattr(write_mod, "compile_events", compile_events),
            setattr(write_mod, "merge_burst_workload", merge_burst))

        def locate(*args, **kwargs):
            self._last["workload"] = compile_events(*args, **kwargs)
            return self._last["workload"]

        def burst_windows(*args, **kwargs):
            self._last["burst"] = merge_burst(*args, **kwargs)
            return self._last["burst"]

        def burst_profiled():
            profs, n_windows = burst_profiles()
            self._last["spart"] = profs.sparts[0]
            return profs, n_windows

        price = session.engine.price

        def priced(table, *args, **kwargs):
            sol = price(table, *args, **kwargs)
            if self._in_window:
                wp = table.profiles.wparts
                self.spans.work["price"].append({
                    "rows": int(np.unique(table.rows).shape[0]),
                    "pages": int(table.profiles.counts.shape[1]),
                    "cells": len(table),
                    "write_rows": sum(w is not None for w in wp)})
            return sol

        sketch = session.sketch
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
        write_mod.compile_events = locate_fn
        write_mod.merge_burst_workload = burst_windows
        session._burst_profiles = burst_profiled
        session.engine.price = priced_fn
        sketch.update = profiled_fn

    # ---------------------------------------------------------------- window
    def begin_window(self) -> None:
        self._in_window = True

    def call(self) -> int:
        """One batch through ``WriteSession.run``; returns its operations."""
        i = self.seq % len(self.batches)
        self.seq += 1
        self._last.clear()
        (rec,) = self.session.run(self.batches[i]).records
        row = {"pool": i, "merged": bool(rec.merged),
               "delta_entries": int(rec.delta_entries),
               "cap_now": int(rec.cap_now), "window": self._in_window,
               "n_reads": int(rec.n_reads), "priced": reference_write.Priced(
                   rec.io_defer, rec.io_merged, rec.merge_io)}
        self.ledger.append(row)
        if self._in_window:
            if rec.merged:
                self.retunes.append(self.seq - 1)
                self.merge_events.append(self._event(self.seq - 1, rec))
            self._sample(self.seq - 1, rec)
        return rec.n_reads + rec.n_writes

    def _sample(self, seq: int, rec) -> None:
        """Seeded reservoir: a uniform sample of the window's batches."""
        self._window_seen += 1
        if len(self.events) < EVENT_SAMPLE:
            slot = len(self.events)
            self.events.append(None)
        else:
            slot = int(self._reservoir_rng.integers(0, self._window_seen))
            if slot >= EVENT_SAMPLE:
                return
        self.events[slot] = self._event(seq, rec)

    def _event(self, seq: int, rec) -> dict:
        """What the comparison keeps of batch ``seq``."""
        wl = self._last["workload"]
        reads = (wl.parts if wl.kind == "mixed" else (wl,))
        burst = self._last.get("burst")
        return {
            "seq": seq, "rec": rec,
            "positions": np.concatenate(
                [p.positions for p in reads if p.kind == "point"]),
            "chunk": self.session.sketch.chunks[-1],
            "burst": burst, "spart": self._last.get("spart")}

    def release(self) -> None:
        """Free the program's state before the reference runs; the sampled
        device arrays come to the host first."""
        for ev in self.events + self.merge_events:
            sp = ev["spart"]
            if sp is not None:
                ev["coverage"] = np.asarray(sp.coverage, np.float64)
                ev["stats"] = np.asarray(
                    [sp.total_refs, sp.distinct_pages, sp.pinned_retouches,
                     sp.min_capacity], np.float64)
                ev["spart"] = None
        self._restore()
        self.session = None

    # ----------------------------------------------------------------- check
    def reference(self, lut_round=None) -> "WriteReference":
        """The reference over this run's pool; ``lut_round`` makes the
        control.  The deployment (key set and index size) is built once."""
        if self._deployment is None:
            cfg = self.config
            self._deployment = reference_write.WriteDeployment(
                self.keys, eps=cfg["index"]["eps"], c_ipp=cfg["c_ipp"],
                page_bytes=cfg["page_bytes"],
                budget_bytes=cfg["budget_bytes"], write=cfg["write"],
                safety=cfg["scheduler"]["safety"])
        return WriteReference(self._deployment, self.keys, self.pool,
                              lut_round)

    def _recorded(self, ev: dict) -> dict:
        """What the timed path produced at a sampled batch."""
        ch, rec, burst = ev["chunk"], ev["rec"], ev["burst"]
        return {
            "positions": ev["positions"], "counts": ch.counts[0],
            "total": float(ch.totals[0]),
            "dac": float(ch.dac_mass[0]) / max(ch.n_queries, 1),
            "lo": None if burst is None else np.asarray(burst.positions),
            "hi": None if burst is None else np.asarray(burst.hi_positions),
            "coverage": ev.get("coverage"), "stats": ev.get("stats"),
            "io_defer": rec.io_defer, "io_merged": rec.io_merged,
            "merge_io": rec.merge_io, "merged": rec.merged}

    def _checked(self) -> List[dict]:
        """The sampled batches and every window merge, each once."""
        seen = {ev["seq"] for ev in self.events}
        return self.events + [ev for ev in self.merge_events
                              if ev["seq"] not in seen]

    def check(self, ref: "WriteReference", program=None) -> Dict[str, float]:
        """The compared numbers.  ``program`` stands in for the recorded
        outputs (the control passes another reference here)."""
        window = self.config["write"]["window_chunks"]
        merged = [row["merged"] for row in self.ledger]
        pool_of = [row["pool"] for row in self.ledger]
        out = {}
        # the delta, the capacity and the merge rule at every window batch
        cap_mism = decision = 0
        entries = 0
        for seq, row in enumerate(self.ledger):
            entries += ref.updates(row["pool"])
            if row["window"]:
                if (row["delta_entries"] != entries
                        or row["cap_now"] != ref.dep.cap_now(entries)):
                    cap_mism += 1
                rule = ref.dep.merges(row["priced"], row["n_reads"], entries,
                                      margin=TIE_REL)
                if rule is not None and row["merged"] != rule:
                    decision += 1
            if row["merged"]:
                entries = 0
        out["capacity_mismatches"] = cap_mism

        mism = burst_mism = 0
        gap = io_gap = 0.0
        checked = self._checked()
        for ev in checked:
            seq = ev["seq"]
            want = ref.event(seq, pool_of, merged, window)
            got = (self._recorded(ev) if program is None
                   else program.event(seq, pool_of, merged, window))
            # locate and the read profile of the batch itself
            pos = got["positions"]
            if pos.shape != want["positions"].shape:
                mism += max(pos.shape[0], want["positions"].shape[0])
                gap = 1.0
            else:
                mism += int(np.sum(pos != want["positions"]))
                gap = max(gap, _rel(np.abs(got["counts"]
                                           - want["counts"]).sum(),
                                    want["total"]),
                          _rel(got["total"] - want["total"], want["total"]),
                          _rel(got["dac"] - want["dac"], want["dac"]))
            # the burst: sorted windows, coverage, (R, N, pinned, widest)
            burst_mism += _burst_mismatches(got, want)
            # pricing and the decision
            io_gap = max(io_gap, *(
                _rel(got[k] - want[k], want[k])
                for k in ("io_defer", "io_merged", "merge_io")))
            rule = want["merged"]
            if rule is not None and bool(got["merged"]) != rule:
                decision += 1
        out["locate_mismatches"] = mism
        out["profile_gap"] = gap
        out["burst_mismatches"] = burst_mism
        out["io_gap"] = io_gap
        out["decision_mismatches"] = decision
        out["_sampled"] = {"batches": len(checked),
                           "window_batches": sum(r["window"]
                                                 for r in self.ledger),
                           "window_merges": len(self.retunes)}
        return out


def _rel(diff: float, want: float) -> float:
    return float(abs(diff) / max(abs(want), 1e-300))


def _burst_mismatches(got: dict, want: dict) -> int:
    """Integer entries of the burst that differ (every one, where the
    window counts differ)."""
    if got["lo"] is None or want["lo"] is None:
        return 0 if got["lo"] is None and want["lo"] is None else 1
    if got["lo"].shape != want["lo"].shape:
        return max(got["lo"].shape[0], want["lo"].shape[0])
    return int(np.sum(got["lo"] != want["lo"])
               + np.sum(got["hi"] != want["hi"])
               + np.sum(got["coverage"] != want["coverage"])
               + np.sum(got["stats"] != want["stats"]))


class WriteReference:
    """Reference profiles, bursts and decisions of the pool's batches."""

    def __init__(self, dep: reference_write.WriteDeployment,
                 keys: np.ndarray, pool, lut_round):
        self.dep = dep
        self.lut_round = lut_round
        self.read_keys = [keys[b.positions[b.reads]] for b in pool]
        self.update_keys = [keys[b.positions[~b.reads]] for b in pool]
        self._profiles: Dict[int, reference_write.ReadProfile] = {}

    def updates(self, i: int) -> int:
        return int(self.update_keys[i].shape[0])

    def profile(self, i: int) -> reference_write.ReadProfile:
        if i not in self._profiles:
            self._profiles[i] = self.dep.read_profile(self.read_keys[i],
                                                      self.lut_round)
        return self._profiles[i]

    def event(self, seq: int, pool_of, merged, window: int) -> dict:
        """What batch ``seq`` should produce, the delta's history given by
        ``merged``; ``merged`` of the result is None for a tie."""
        dep = self.dep
        prof = self.profile(pool_of[seq])
        first, last = reference_write.delta_since(seq, merged)
        staged = [self.update_keys[pool_of[s]]
                  for s in range(first, last + 1)]
        entries = sum(k.shape[0] for k in staged)
        burst = dep.burst(np.concatenate(staged)) if entries else None
        profiles = [self.profile(pool_of[s])
                    for s in reference_write.window_of(seq, window)]
        priced = dep.price(profiles, entries, burst)
        return {
            "positions": prof.positions, "counts": prof.counts,
            "total": prof.total, "dac": prof.dac,
            "lo": None if burst is None else burst.lo,
            "hi": None if burst is None else burst.hi,
            "coverage": None if burst is None else burst.coverage,
            "stats": None if burst is None else burst.stats,
            "io_defer": priced.io_defer, "io_merged": priced.io_merged,
            "merge_io": priced.merge_io,
            "merged": dep.merges(priced, prof.positions.shape[0], entries,
                                 margin=TIE_REL)}
