"""Device time per frame of the AR reconstruct kernel: the summed
durations of the device's module events of ``ar_reconstruct`` over the
window's frames, in ms."""
import arspans

MODULE = "jit_ar_reconstruct"


def read(ctx):
    return arspans.module_ms_per_frame(ctx, MODULE)
