"""The program's own spans and counters (``repro.obs``), as the per-layer
readers see them.  Shared by the readers of ``unpack_ms``, ``search_ms``,
``route_ms``, ``profile_prep_ms``, ``profile_wait_ms``, ``profile_rows_ms``,
``chunk_ms``, ``loop_ms``, ``price_marshal_ms``, ``host_syncs_per_batch``,
``host_sync_bytes_per_batch`` and ``idle_unattributed``.

A traced run leaves the program's registry in memory.  :class:`Program`
clips it to the window: a batch is a ``serving.observe`` root span that
lies inside the ``bench.window`` interval of ``ctx["spans"]``, and only the
spans and counts of those batches are read.  A program that records no
such spans (one older than ``repro.obs``) gives every reader None, and so
does a run off the chip: these numbers split the chip's served path, and a
CPU rehearsal's interpret-mode kernels say nothing about it.
"""
from __future__ import annotations

import os
from typing import Iterable, Optional

import tracing

#: Where ``run.py`` writes each cell's trace.
TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")
#: The program span that is one served batch.
ROOT = "serving.observe"
#: Every span the program opens on the served path.
SPAN_NAMES = (ROOT, "trace.compile", "trace.unpack", "workload.locate",
              "sketch.update", "profile.route", "profile.prep",
              "profile.wait", "profile.rows", "sketch.chunk",
              "serving.detect", "serving.retune", "sketch.merge",
              "engine.price", "price.marshal", "price.wait")


class Program:
    """The spans and counts of the batches that ran inside ``window``
    (host-clock seconds), from a registry as ``repro.obs.snapshot`` gives
    it: spans ``(name, t0, t1, parent, root)``, counts
    ``(name, t, n, span)``."""

    def __init__(self, registry: dict, window):
        lo, hi = window
        spans = registry["spans"]
        roots = {i for i, s in enumerate(spans)
                 if s[0] == ROOT and s[3] is None and s[2] is not None
                 and lo <= s[1] and s[2] <= hi}
        self.batches = len(roots)
        self.spans = [s for s in spans if s[4] in roots]
        self.counts = [c for c in registry["counts"]
                       if c[3] is not None and spans[c[3]][4] in roots]

    def _ms(self, names: Iterable[str]):
        names = set(names)
        return [1e3 * (s[2] - s[1]) for s in self.spans if s[0] in names]

    def per_batch_ms(self, name: str) -> Optional[float]:
        """Milliseconds in spans ``name``, summed per batch, mean over
        batches; None where no batch opened one."""
        ms = self._ms([name])
        return sum(ms) / self.batches if ms else None

    def per_call_ms(self, name: str) -> Optional[float]:
        """Mean milliseconds of one span ``name``."""
        ms = self._ms([name])
        return sum(ms) / len(ms) if ms else None

    def outside_ms(self, inner: Iterable[str]) -> Optional[float]:
        """Milliseconds per batch in the root span outside the spans
        ``inner`` (which must not nest in one another)."""
        if not self.batches:
            return None
        return (sum(self._ms([ROOT])) - sum(self._ms(inner))) / self.batches

    def per_batch_count(self, name: str) -> Optional[float]:
        """Counter ``name`` summed over the batches, per batch."""
        if not self.batches:
            return None
        return sum(c[2] for c in self.counts if c[0] == name) / self.batches


def program(ctx) -> Optional[Program]:
    """The running program's registry clipped to this run's window, or
    None off the chip, where the program records no spans, or where no
    batch ran inside the window."""
    if ctx["device"].get("platform") != "tpu":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    (window,) = ctx["spans"].intervals[tracing.WINDOW_SPAN]
    prog = Program(obs.snapshot(), window)
    return prog if prog.batches else None


def unattributed_share(trace: tracing.Trace) -> Optional[float]:
    """Device idle inside the window while no program span is open, as %
    of all device idle there; None without device ops or program spans."""
    if not trace.device_ops or not any(s[0] == ROOT
                                       for s in trace.host_spans):
        return None
    idle = trace.idle_by_span()
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * idle.get("outside spans", 0.0) / total


def idle_unattributed(trace_dir: str = TRACES) -> Optional[float]:
    """:func:`unattributed_share` of the newest trace under ``trace_dir``,
    reduced against the program's span names."""
    path = tracing.newest_xplane(trace_dir)
    if path is None:
        return None
    return unattributed_share(tracing.reduce_trace(path, SPAN_NAMES))
