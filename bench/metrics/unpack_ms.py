"""Trace compile and locate: host milliseconds per batch turning the
batch's ``TraceEvent``s into key arrays (program span ``trace.unpack``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("trace.unpack")
