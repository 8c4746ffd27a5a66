"""Device: idle time inside the window during which no program span is
open, as % of all device idle there."""
import program_spans


def read(ctx):
    return program_spans.idle_unattributed()
