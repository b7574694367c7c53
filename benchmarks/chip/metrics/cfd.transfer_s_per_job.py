"""Host time per job in copying slabs between the host and the device:
the program's ``lbm.h2d`` and ``lbm.d2h`` spans less the device's busy
time inside them (``lbm.d2h`` waits for the step it reads), over the
window's jobs, in s."""
import progspans

REQUEST = "bench.job"
SPANS = ("lbm.h2d", "lbm.d2h")


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.host_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e9
