"""Trace compile and locate (``serving/trace.py``, ``write/session.py``):
host milliseconds per batch, mean over the window's batches."""


def read(ctx):
    return ctx["spans"].mean_ms("locate")
