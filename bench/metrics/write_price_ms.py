"""Pricing engine and table marshalling on the write path: host
milliseconds per batch in ``engine.price`` (the decision event's one
three-cell call)."""
import write_spans


def read(ctx):
    prog = write_spans.program(ctx)
    return None if prog is None else prog.per_batch_ms("engine.price")
