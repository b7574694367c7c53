"""Host time per frame in handing the kernels' inputs to the device: the
program's ``ar.h2d`` spans (inside ``pocl.kernel``) over the window's
frames, in us. ``jax.device_put`` returns once the copies are under way,
so what a copy takes after that shows where the host next waits on the
device, in the commit (``passthrough.commit_us_per_chain``)."""
import arspans

SPANS = ("ar.h2d",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = arspans.time_ns(ctx.trace, SPANS)
    per = arspans.per_frame(ctx.trace, ns)
    return None if per is None else per / 1e3
