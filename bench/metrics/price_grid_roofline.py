"""Fused solve (``kernels/price_grid.py``): share of its roofline.

The least work a price call needs, counted from the public ``PriceTable``:
each priced row's float32 page histogram read once (two for a row with a
write stream), plus each cell's capacity in and hit rate out.  The solve
is elementwise and reductions, not matrix math, so the bound is bytes
over peak HBM bandwidth.
"""
import roofline

#: How the kernel's events are named in the device trace.
KERNEL = "price_grid"


def least_bytes(rows: int, pages: int, cells: int, write_rows: int) -> int:
    return 4 * pages * (rows + write_rows) + 8 * cells


def read(ctx):
    work = sum(least_bytes(**w) for w in ctx["spans"].work["price"])
    return roofline.share(ctx, KERNEL, work)
