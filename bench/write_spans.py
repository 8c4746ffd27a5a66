"""The program's own spans and counters on the write path, as the write
cell's per-layer readers see them.  Shared by the readers of
``write_profile_ms``, ``burst_ms``, ``write_price_ms``, ``write_loop_ms``,
``write_compiles_per_batch`` and ``write_pad_share``.

The window clip of :class:`program_spans.Program`, with a batch being a
``write.batch`` root span (``WriteSession._batch``) inside the
``bench.window`` interval.  As there, a program that records no such spans
gives every reader None, and so does a run off the chip.
"""
from __future__ import annotations

from typing import Iterable, Optional

import program_spans
import tracing

#: The program span that is one served write batch.
ROOT = "write.batch"


class WriteProgram(program_spans.Program):
    """:class:`program_spans.Program` over ``write.batch`` roots."""

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

    def outside_ms(self, inner: Iterable[str]) -> Optional[float]:
        """Milliseconds per batch in ``write.batch`` outside the spans
        ``inner`` (which must not nest in one another)."""
        if not self.batches:
            return None
        return (sum(self._ms([ROOT])) - sum(self._ms(inner))) / self.batches


def program(ctx) -> Optional[WriteProgram]:
    """The running program's registry clipped to this run's window, or
    None off the chip, where the program records no spans, or where no
    write batch ran inside the window."""
    if ctx["device"].get("platform") != "tpu":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    (window,) = ctx["spans"].intervals[tracing.WINDOW_SPAN]
    prog = WriteProgram(obs.snapshot(), window)
    return prog if prog.batches else None
