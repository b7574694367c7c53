"""Bytes copied between the host and the device per job: the ``bytes``
stats of the program's spans (``lbm.h2d``, ``lbm.d2h``, and
``pocl.commit`` where it copies a device array) over the window's jobs,
in GB (1e9 bytes)."""
import progspans

REQUEST = "bench.job"
STAT = "bytes"


def read(ctx):
    if ctx.trace is None:
        return None
    n = progspans.stat_sum(ctx.window.trace_file, ctx.trace, STAT)
    per = progspans.per_request(ctx.trace, REQUEST, n)
    return None if per is None else per / 1e9
