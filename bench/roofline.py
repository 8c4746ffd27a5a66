"""Roofline share of a kernel: the least time its work needs on the chip
over the device time its trace events took.  Shared by the
``<kernel>_roofline`` readers, which supply the work count."""


def share(ctx, kernel_fragment, least_bytes):
    """``least_bytes`` over peak HBM bandwidth, as % of the kernel's time;
    None where the window holds no such kernel or no work."""
    trace = ctx["trace"]
    if trace is None or least_bytes <= 0:
        return None
    seconds = trace.kernel_seconds(kernel_fragment)
    if seconds <= 0:
        return None
    peaks = ctx["peaks"]
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return 100.0 * least_bytes / peaks[kind]["hbm_bytes_per_s"] / seconds
