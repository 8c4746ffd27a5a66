"""Occupancy profiling (``WindowSketch.update``): host milliseconds per
batch until the batch's histograms are host arrays, mean over the window."""


def read(ctx):
    return ctx["spans"].mean_ms("profile")
