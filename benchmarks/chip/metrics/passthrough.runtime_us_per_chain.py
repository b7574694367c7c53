"""The runtime's own host time per chain: the self time of the program's
command-path spans (``pocl.enqueue_write``, ``pocl.enqueue_kernel``,
``pocl.enqueue_read``, ``pocl.finish``), which leaves out the kernel call
(``pocl.kernel``) and the output commit (``pocl.commit``) nested in the
drain, over the window's chains, in us."""
import progspans

REQUEST = "bench.chain"
SPANS = ("pocl.enqueue_write", "pocl.enqueue_kernel", "pocl.enqueue_read",
         "pocl.finish")


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.self_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e3
