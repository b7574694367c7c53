"""Host time per chain in the call of the kernel's function (for the
copy: the input's transfer to the chip and the jitted call's dispatch):
the self time of the program's ``pocl.kernel`` spans over the window's
chains, in us."""
import progspans

REQUEST = "bench.chain"
SPANS = ("pocl.kernel",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.self_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e3
