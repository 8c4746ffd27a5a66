"""Occupancy profiling on the write path: host milliseconds per batch in
``sketch.update`` (the batch's reads profiled on the uniform-eps path)."""
import write_spans


def read(ctx):
    prog = write_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("sketch.update")
