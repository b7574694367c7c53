"""The AR reconstruct kernel's share of its roofline, in %: the least
time the chip could take for the point slots of the window's calls, over
the kernel's device time in the window.

Each point slot reads its three uint16 voxel coordinates (6 bytes) and
writes its float32 depth key (4 bytes), 10 bytes, and does some 30
operations on them, so HBM bounds it: the least time is 10 bytes per
slot over the chip's peak HBM bandwidth. Every call covers the cell's
``capacity`` slots, padding included. The bytes come from the work, not
from the implementation, so the share moves only with the kernel's
time."""
import arspans

MODULE = "jit_ar_reconstruct"
BYTES_PER_SLOT = 3 * 2 + 4


def read(ctx):
    return arspans.roofline(ctx, MODULE, BYTES_PER_SLOT)
