"""Median wall time of a command chain, from its first enqueue to
``finish()`` returning, over all chains of the window, in us (host clock
around each chain; the untraced window)."""
import numpy as np

Q = 50


def read(ctx):
    w = ctx.window
    return float(np.percentile(np.subtract(w.ends, w.starts), Q)) * 1e6
