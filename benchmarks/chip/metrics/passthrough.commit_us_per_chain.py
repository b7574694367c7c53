"""Host time per chain in committing the kernel's output to its buffer
(``np.asarray`` of the device array: the wait for the kernel and the
copy to the host): the self time of the program's ``pocl.commit`` spans
over the window's chains, in us."""
import progspans

REQUEST = "bench.chain"
SPANS = ("pocl.commit",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.self_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e3
