"""Host time per job in splitting the client's lattice into halo-padded
slabs: the program's ``lbm.split`` spans over the window's jobs, in s."""
import progspans

REQUEST = "bench.job"
SPANS = ("lbm.split",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.time_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e9
