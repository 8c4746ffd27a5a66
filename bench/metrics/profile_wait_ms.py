"""Occupancy kernel: host milliseconds per batch waiting on the first
read of ``profile_grid``'s output (program span ``profile.wait``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("profile.wait")
