"""The CFD kernel's share of its roofline, in %: the least time the chip
could take for the cell updates of the window, over the kernel's device
time in the window.

A D2Q9 BGK update reads and writes nine f32 populations of a cell, 72
bytes, and does some 200 operations on them, so HBM bounds it: the least
time is 72 bytes per cell update over the chip's peak HBM bandwidth. The
bytes come from the work, not from the implementation, so the share moves
only with the kernel's time."""
import peaks
import tracereduce

MODULE = "jit_lbm_step"
BYTES_PER_UPDATE = 2 * 9 * 4


def read(ctx):
    if ctx.trace is None:
        return None
    evs = tracereduce.module_events(ctx.trace, MODULE)
    if not evs:
        return None
    kernel_s = sum(e - s for _, _, s, e in evs) / 1e9
    least_s = (BYTES_PER_UPDATE * sum(ctx.window.work)
               / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / kernel_s
