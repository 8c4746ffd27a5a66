"""Compiles on the write path: program counter ``compile`` (two per fresh
``jit``: its trace and its backend compile) over the window's batches."""
import write_spans


def read(ctx):
    prog = write_spans.program(ctx)
    return None if prog is None else prog.per_batch_count("compile")
