"""Occupancy kernel (``kernels/profile_grid.py``): share of its roofline.

The least work a profile call needs, counted from the problem's sizes
(recorded from the public ``Workload`` and the returned chunk, never from
the kernel's padded operands): the batch's query positions read once as
int32, and K x P float32 page histograms written once.  None of it is
matrix math, so the bound is bytes over peak HBM bandwidth.
"""
import roofline

#: How the kernel's events are named in the device trace.
KERNEL = "profile_grid"


def least_bytes(queries: int, rows: int, pages: int) -> int:
    return 4 * queries + 4 * rows * pages


def read(ctx):
    work = sum(least_bytes(**w) for w in ctx["spans"].work["profile"])
    return roofline.share(ctx, KERNEL, work)
