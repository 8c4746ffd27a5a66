"""Trace compile and locate: host milliseconds per batch in the key
file's ``searchsorted`` (program span ``workload.locate``, every call)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("workload.locate")
