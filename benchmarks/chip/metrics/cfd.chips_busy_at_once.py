"""How many of the cell's chips work at once, on average over the time in
which any of them works: the sum over the chips of each one's busy
seconds in the window, over the seconds of the union of all the chips'
busy intervals. It reads 1 where the servers' steps run one after
another, and the number of chips where they all run together."""
import tracereduce


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy.values()
    union = sum(e - s for s, e in tracereduce.merge(
        iv for ivs in busy for iv in ivs))
    if union == 0:
        return None
    return sum(e - s for ivs in busy for s, e in ivs) / union
