"""Host time per job in putting the stepped slabs back together into the
client's lattice: the program's ``lbm.concatenate`` spans over the
window's jobs, in s."""
import progspans

REQUEST = "bench.job"
SPANS = ("lbm.concatenate",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.time_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e9
