"""Host time per chain in making the read buffer host bytes for the
client (the wait for the kernels that wrote it and its copy off the
device, content size first): the self time of the program's
``pocl.read`` spans over the window's chains, in us. A program that
copies its outputs off the device at the commit opens no such span, and
this reads nothing."""
import progspans

REQUEST = "bench.chain"
SPANS = ("pocl.read",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.self_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e3
