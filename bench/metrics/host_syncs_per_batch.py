"""Device transfers: device-to-host reads per batch (program counter
``host_sync``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_count("host_sync")
