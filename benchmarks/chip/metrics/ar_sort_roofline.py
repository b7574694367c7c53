"""The AR depth sort's share of its roofline, in %: the least time the
chip could take for the point slots of the window's calls, over the
kernel's device time in the window.

Any sort must at least read each float32 key (4 bytes) and write each
uint32 index (4 bytes), 8 bytes a slot; that least traffic at the
chip's peak HBM bandwidth is the least time. A comparison sort moves
the keys some log2(n) times, so the share is low by nature; it moves
only with the kernel's time. Every call covers the cell's ``capacity``
slots, padding included."""
import arspans

MODULE = "jit_ar_sort"
BYTES_PER_SLOT = 4 + 4


def read(ctx):
    return arspans.roofline(ctx, MODULE, BYTES_PER_SLOT)
