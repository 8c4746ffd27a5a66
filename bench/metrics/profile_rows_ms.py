"""Occupancy profiling: host milliseconds per batch splitting the kernel's
output into per-knob rows and restacking them (program span
``profile.rows``, both calls)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("profile.rows")
