"""Host time per job in the client's halo exchange between steps: the
program's ``lbm.exchange_halos`` spans over the window's jobs, in s."""
import progspans

REQUEST = "bench.job"
SPANS = ("lbm.exchange_halos",)


def read(ctx):
    if ctx.trace is None:
        return None
    ns = progspans.time_ns(ctx.trace, SPANS)
    per = progspans.per_request(ctx.trace, REQUEST, ns)
    return None if per is None else per / 1e9
