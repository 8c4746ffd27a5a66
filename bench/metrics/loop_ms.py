"""Serving loop: host milliseconds per batch in ``serving.observe`` outside
trace compile, the sketch update and the engine's price calls (drift
check, merge, table build, decision)."""
import program_spans

INNER = ("trace.compile", "sketch.update", "engine.price")


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.outside_ms(INNER)
