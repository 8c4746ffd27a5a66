"""Occupancy profiling: host milliseconds per batch routing the batch
through each RMI knob for its leaf errors and E[DAC] (program span
``profile.route``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("profile.route")
