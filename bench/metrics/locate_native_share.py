"""Trace compile and locate: share of the key-file searches that ran in
the key file's own dtype (program counter ``locate.native`` over the
``workload.locate`` calls of the window's batches), in %.  A program that
counts no ``locate.native`` reads 0."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    if prog is None:
        return None
    calls = sum(1 for s in prog.spans if s[0] == "workload.locate")
    if not calls:
        return None
    native = sum(c[2] for c in prog.counts if c[0] == "locate.native")
    return 100.0 * native / calls
