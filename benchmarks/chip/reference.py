"""The plain reference of the CFD deployment: D2Q9 BGK collide-and-stream
with periodic boundaries in float64 numpy. It imports nothing of the
program; the benchmark keeps its own copy so that no change to the
program can move the yardstick.

Velocities are ``(cx, cy)`` along (width, height); a population with
velocity ``c`` moves by ``cx`` columns and ``cy`` rows per step.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

C = np.array([[0, 0], [1, 0], [0, 1], [-1, 0], [0, -1],
              [1, 1], [-1, 1], [-1, -1], [1, -1]])
W = np.array([4 / 9] + [1 / 9] * 4 + [1 / 36] * 4)


def steps(f: np.ndarray, n: int, tau: float) -> np.ndarray:
    """``n`` steps of ``f`` [9, H, W] in float64."""
    f = np.array(f, dtype=np.float64)
    for _ in range(n):
        rho = f.sum(axis=0)
        ux = (f[1] + f[5] + f[8] - f[3] - f[6] - f[7]) / rho
        uy = (f[2] + f[5] + f[6] - f[4] - f[7] - f[8]) / rho
        usq = ux * ux + uy * uy
        out = np.empty_like(f)
        for q, ((cx, cy), w) in enumerate(zip(C, W)):
            cu = cx * ux + cy * uy
            feq = w * rho * (1 + 3 * cu + 4.5 * cu * cu - 1.5 * usq)
            out[q] = np.roll(f[q] + (feq - f[q]) / tau, (cy, cx),
                             axis=(0, 1))
        f = out
    return f


def max_abs_error(got: np.ndarray, f0: np.ndarray, n: int, tau: float,
                  band: int = 128) -> float:
    """max|got - steps(f0, n)| over the whole lattice, computed in row
    bands on every host core so that a full-size lattice fits in host
    memory. After ``n`` steps a row depends only on the ``n`` rows either
    side, so each band is stepped with that margin and its interior
    compared."""
    H = f0.shape[1]
    if got.shape != f0.shape:
        return float("inf")

    def band_error(lo: int) -> float:
        hi = min(lo + band, H)
        rows = np.arange(lo - n, hi + n) % H
        ref = steps(f0[:, rows], n, tau)[:, n:n + hi - lo]
        err = np.abs(got[:, lo:hi] - ref).max()
        return float(err) if np.isfinite(err) else float("inf")

    # a band's working set is some 0.4 GB: eight at a time keep the check
    # within a few GB of host memory beside the lattices it compares
    with ThreadPoolExecutor(min(8, len(os.sched_getaffinity(0)))) as ex:
        return max(ex.map(band_error, range(0, H, band)))
