"""Device time per frame of the AR depth sort: the summed durations of
the device's module events of ``ar_sort`` over the window's frames, in
ms."""
import arspans

MODULE = "jit_ar_sort"


def read(ctx):
    return arspans.module_ms_per_frame(ctx, MODULE)
