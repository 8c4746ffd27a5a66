"""Lane buckets on the write path: padded lanes as % of all lanes profiled
(program counters ``profile.pad_lanes`` and ``profile.lanes``).  A program
that counts no lanes reads nothing."""
import write_spans


def read(ctx):
    prog = write_spans.program(ctx)
    if prog is None:
        return None
    lanes = prog.per_batch_count("profile.lanes")
    pad = prog.per_batch_count("profile.pad_lanes")
    if lanes + pad <= 0:
        return None
    return 100.0 * pad / (lanes + pad)
