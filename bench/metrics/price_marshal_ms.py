"""Pricing engine and table marshalling: host milliseconds per price
call up to and including the ``price_grid`` launch (program span
``price.marshal``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_call_ms("price.marshal")
