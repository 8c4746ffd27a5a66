"""Occupancy profiling: host milliseconds per batch copying the
histograms to the host and summarising the batch for drift (program span
``sketch.chunk``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("sketch.chunk")
