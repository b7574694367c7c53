"""Share of the traced window in which no operation ran on the device:
1 - busy/window per chip, averaged over the cell's chips, in %."""
import tracereduce


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * tracereduce.idle_share(ctx.trace)
