"""Lattice-cell updates completed in the window, over the window's wall
seconds, over the cell's chips, in millions (MLUPs/s per chip). The
window is made of whole jobs, and each job's work is its cell updates."""


def read(ctx):
    w = ctx.window
    return sum(w.work) / w.seconds / ctx.cell.chips / 1e6
