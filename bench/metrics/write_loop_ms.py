"""Write loop: host milliseconds per batch in ``write.batch`` outside trace
compile, the sketch update and the price event (staging, the decision and
the merge bookkeeping)."""
import write_spans

INNER = ("trace.compile", "sketch.update", "write.price_event")


def read(ctx):
    prog = write_spans.program(ctx)
    return None if prog is None else prog.outside_ms(INNER)
