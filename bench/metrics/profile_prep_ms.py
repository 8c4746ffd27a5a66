"""Occupancy profiling: host milliseconds per batch preparing and
launching ``profile_grid`` (class codes, dense rank, the LUT stack, the
transfers; program span ``profile.prep``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("profile.prep")
