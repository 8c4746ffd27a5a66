"""Merge burst: host milliseconds per batch in ``write.burst`` (the staged
ranks compiled into sorted windows and profiled)."""
import write_spans


def read(ctx):
    prog = write_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("write.burst")
