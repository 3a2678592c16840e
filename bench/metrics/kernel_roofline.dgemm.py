"""Fused kernel's share of its roofline, in %: the least time of the window's
calls (the paper's residue-product count on the MXU they run on, eq. 17, or
the kernel's least HBM traffic where that is longer) over the device time
of all Pallas kernel events in the window."""


def read(trace, counts):
    if trace.kernel_s <= 0 or "least_kernel_s_per_call" not in counts:
        return None
    return 100.0 * counts["least_kernel_s_per_call"] * counts["calls"] / trace.kernel_s
