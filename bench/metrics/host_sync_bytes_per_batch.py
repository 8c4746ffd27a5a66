"""Device transfers: bytes read from the device to the host per batch
(program counter ``host_sync_bytes``)."""
import program_spans


def read(ctx):
    prog = program_spans.program(ctx)
    return None if prog is None else prog.per_batch_count("host_sync_bytes")
