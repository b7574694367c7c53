"""Bytes copied off the device per frame by the runtime's commits: the
``bytes`` stats of the program's ``pocl.commit`` spans (the only
``pocl.*`` or ``lbm.*`` spans that carry one in this cell) over the
window's frames (``bench.chain``), in MB (1e6 bytes). An output whose
content-size buffer the same kernel writes counts only what of it left
the device."""
import progspans

REQUEST = "bench.chain"
STAT = "bytes"


def read(ctx):
    if ctx.trace is None:
        return None
    n = progspans.stat_sum(ctx.window.trace_file, ctx.trace, STAT)
    per = progspans.per_request(ctx.trace, REQUEST, n)
    return None if per is None else per / 1e6
