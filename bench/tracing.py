"""Host spans around the layers, and the reduction of a profiler trace.

Spans are kept in memory by :class:`Spans`: each records its host-clock
interval and, in a traced run, also enters a ``jax.profiler.TraceAnnotation``
so that it lands on the trace's clock beside the device's operations.

:func:`reduce_trace` reads the ``.xplane.pb`` the JAX profiler writes and
returns the device's busy intervals, the time of each device operation by
name, and the host spans, all inside the traced window.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import time
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"


class Spans:
    """Host-clock spans by name; ``annotate`` also writes them to the trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.intervals: Dict[str, List[Tuple[float, float]]] = \
            collections.defaultdict(list)
        self.work: Dict[str, List[dict]] = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.intervals[name].append((t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def mean_ms(self, name: str) -> Optional[float]:
        """Mean duration of the spans that lie inside the window span."""
        (lo, hi), = self.intervals[WINDOW_SPAN]
        iv = [(a, b) for a, b in self.intervals.get(name, ())
              if lo <= a and b <= hi]
        if not iv:
            return None
        return 1e3 * sum(b - a for a, b in iv) / len(iv)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def union_length(intervals: List[Tuple[int, int]]) -> Tuple[int, list]:
    """Total covered length and the merged intervals (sorted)."""
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [tuple(m) for m in merged]


class Trace:
    """What the metrics read from one traced window (times in ns)."""

    def __init__(self, window: Tuple[int, int],
                 device_ops: Dict[int, List[Tuple[str, int, int]]],
                 host_spans: List[Tuple[str, int, int]]):
        self.window = window
        self.device_ops = device_ops      # device id -> [(name, start, end)]
        self.host_spans = host_spans      # [(name, start, end)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, ops):
        lo, hi = self.window
        return [(max(a, lo), min(b, hi)) for _, a, b in ops
                if b > lo and a < hi]

    def busy_s(self) -> float:
        """Union of device-op intervals inside the window, mean over chips."""
        if not self.device_ops:
            return 0.0
        per = [union_length(self._clipped(ops))[0]
               for ops in self.device_ops.values()]
        return 1e-9 * sum(per) / len(per)

    def op_seconds(self) -> Dict[str, float]:
        """Summed device time by operation (all chips).  The trace names an
        op by its HLO text; the name is its result's, before `` = ``."""
        lo, hi = self.window
        out: Dict[str, float] = collections.defaultdict(float)
        for ops in self.device_ops.values():
            for name, a, b in ops:
                if b > lo and a < hi:
                    out[name.split(" = ", 1)[0]] += (min(b, hi)
                                                     - max(a, lo)) * 1e-9
        return dict(out)

    def kernel_seconds(self, fragment: str) -> float:
        return sum(s for n, s in self.op_seconds().items() if fragment in n)

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device time inside the window, by the innermost host span
        open at each gap's midpoint (``outside spans`` where none is)."""
        if not self.device_ops:
            return {}
        ops = next(iter(self.device_ops.values()))
        _, merged = union_length(self._clipped(ops))
        lo, hi = self.window
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        # one host thread: spans nest, so the innermost span open at a time
        # is the latest-started one that has not ended
        spans = sorted((s for s in self.host_spans if s[0] != WINDOW_SPAN),
                       key=lambda s: s[1])
        starts = [s[1] for s in spans]
        out: Dict[str, float] = collections.defaultdict(float)
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            name = "outside spans"
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(i - 64, -1), -1):
                if spans[j][2] > mid:
                    name = spans[j][0]
                    break
            out[name] += (b - a) * 1e-9
        return dict(out)


def reduce_trace(path: str, span_names) -> Trace:
    """Device ops and host spans of the window marked by ``WINDOW_SPAN``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Tuple[str, int, int]]] = {}
    host: List[Tuple[str, int, int]] = []
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                device_ops.setdefault(dev, []).extend(
                    (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns)) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    windows = [s for s in host if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    window = (windows[0][1], windows[0][2])
    return Trace(window, device_ops, host)

