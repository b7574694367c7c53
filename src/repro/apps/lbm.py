"""D2Q9 lattice-Boltzmann (BGK) in JAX — the FluidX3D case-study payload
(paper §7.2).

The lattice is decomposed along x into halo-padded slabs. ``run_offloaded``
is the CFD offload loop: the client writes the slabs to its servers,
each server steps its slab with the real kernel on its own device, and
the halos are exchanged on the host-side buffers between steps.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ClientRuntime, DeviceSpec, LinkSpec, ServerSpec
from repro.core.trace import span

# D2Q9 velocities and weights
C = np.array([[0, 0], [1, 0], [0, 1], [-1, 0], [0, -1],
              [1, 1], [-1, 1], [-1, -1], [1, -1]])
W = np.array([4 / 9] + [1 / 9] * 4 + [1 / 36] * 4)

# At DEFAULT precision the TPU rounds the whole f32 distribution to bf16
# before these contractions (a ~2^-9 relative error in u); HIGHEST keeps f32.
_F32 = jax.lax.Precision.HIGHEST

# the paper's CFD testbed (§7.2): 1 GbE to the client, 100 GbE between servers
_CLIENT_LINK = LinkSpec(latency=50e-6, bandwidth=1e9 / 8)
_PEER_LINK = LinkSpec(latency=10e-6, bandwidth=100e9 / 8)


def equilibrium(rho: jax.Array, u: jax.Array) -> jax.Array:
    """rho [H,W], u [2,H,W] → feq [9,H,W]."""
    cu = jnp.einsum("qd,dhw->qhw", jnp.asarray(C, u.dtype), u,
                    precision=_F32)
    usq = jnp.sum(u * u, axis=0)
    w = jnp.asarray(W, u.dtype)[:, None, None]
    return w * rho * (1 + 3 * cu + 4.5 * cu ** 2 - 1.5 * usq)


def macroscopic(f: jax.Array):
    rho = jnp.sum(f, axis=0)
    u = jnp.einsum("qd,qhw->dhw", jnp.asarray(C, f.dtype), f,
                   precision=_F32) / jnp.maximum(rho, 1e-12)
    return rho, u


@functools.partial(jax.jit, static_argnames=("tau",))
def lbm_step(f: jax.Array, tau: float = 0.6) -> jax.Array:
    """One collide-and-stream step with periodic boundaries. f: [9,H,W]."""
    rho, u = macroscopic(f)
    feq = equilibrium(rho, u)
    f = f + (feq - f) / tau
    # streaming: shift each population along its velocity
    outs = [jnp.roll(f[q], shift=(int(C[q][1]), int(C[q][0])),
                     axis=(0, 1)) for q in range(9)]
    return jnp.stack(outs)


def init_shear(H: int, W_: int, dtype=jnp.float32) -> jax.Array:
    """Double shear layer initial condition."""
    y = jnp.arange(H)[:, None] / H
    x = jnp.arange(W_)[None, :] / W_
    ux = 0.05 * jnp.tanh((y - 0.5) * 20) * jnp.ones_like(x)
    uy = 0.01 * jnp.sin(2 * jnp.pi * x) * jnp.ones_like(y)
    u = jnp.stack([ux, uy]).astype(dtype)
    rho = jnp.ones((H, W_), dtype)
    return equilibrium(rho, u)


def reference_steps(f: np.ndarray, steps: int,
                    tau: float = 0.6) -> np.ndarray:
    """Plain float64 numpy D2Q9 BGK with periodic boundaries: the
    independent reference ``lbm_step`` is checked against."""
    f = np.array(f, dtype=np.float64)
    for _ in range(steps):
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


def reference_max_error(got: np.ndarray, f0: np.ndarray, steps: int,
                        band: int = 256) -> float:
    """max|got - reference_steps(f0, steps)|, computed in row bands on
    all host cores so a full-size lattice fits in host memory. After
    ``steps`` steps a row depends only on the ``steps`` rows either side,
    so each band is stepped with that margin and its interior compared."""
    H = f0.shape[1]

    def band_error(lo: int) -> float:
        hi = min(lo + band, H)
        rows = np.arange(lo - steps, hi + steps) % H
        ref = reference_steps(f0[:, rows], steps)[:, steps:-steps or None]
        return float(np.abs(got[:, lo:hi] - ref).max())

    with ThreadPoolExecutor(len(os.sched_getaffinity(0))) as ex:
        return max(ex.map(band_error, range(0, H, band)))


# ---------------- domain decomposition ----------------

def split_domain(f: np.ndarray, n: int) -> list:
    """Split [9,H,W] along W into n slabs, each padded with 1-col periodic
    halos. Each slab is a fresh C-contiguous array filled by slice copies:
    the interior in one, then the two halo columns."""
    W_ = f.shape[2]
    if W_ % n:
        raise ValueError(f"width {W_} does not split into {n} slabs")
    w = W_ // n
    slabs = []
    with span("lbm.split"):
        for lo in range(0, W_, w):
            slab = np.empty(f.shape[:2] + (w + 2,), f.dtype)
            slab[:, :, 1:-1] = f[:, :, lo:lo + w]
            slab[:, :, 0] = f[:, :, (lo - 1) % W_]
            slab[:, :, -1] = f[:, :, (lo + w) % W_]
            slabs.append(slab)
    return slabs


def exchange_halos(slabs: list) -> list:
    """Copy boundary columns between neighbours (periodic)."""
    n = len(slabs)
    out = []
    with span("lbm.exchange_halos"):
        for i in range(n):
            left_src = slabs[(i - 1) % n][:, :, -2:-1]   # last interior col
            right_src = slabs[(i + 1) % n][:, :, 1:2]    # first interior col
            core = slabs[i][:, :, 1:-1]
            out.append(np.concatenate([left_src, core, right_src], axis=2))
    return out


@dataclasses.dataclass
class OffloadRun:
    f: np.ndarray          # [9,H,W] lattice after the last step
    devices: list          # per server: devices its kernel outputs came from
    step_seconds: list     # host wall s per step, ended by the host copy
    stats: dict            # ClientRuntime.stats(): simulated clock


def run_offloaded(f0, n_servers: int, steps: int) -> OffloadRun:
    """Step ``f0`` through ``ClientRuntime`` on ``n_servers`` servers.

    Server ``i`` computes on ``jax.local_devices()[i % count]``: its slab
    is committed there before the jitted ``lbm_step``, after which the
    slab's interior columns are valid. Each step enqueues the slab kernels
    and reads, waits for them, exchanges halos between the host-side
    buffers and writes the slabs back.

    The host's phases are spans (``core.trace.span``): ``lbm.split``
    and ``lbm.exchange_halos`` (in their functions), ``lbm.h2d`` and
    ``lbm.d2h`` (each slab's copy to its device and back, with its
    ``server`` and ``bytes``) and ``lbm.concatenate``."""
    local = jax.local_devices()
    devs = [local[i % len(local)] for i in range(n_servers)]
    names = [f"s{i}" for i in range(n_servers)]
    rt = ClientRuntime(
        servers=[ServerSpec(name, [DeviceSpec(d.device_kind)])
                 for name, d in zip(names, devs)],
        client_link=_CLIENT_LINK, peer_link=_PEER_LINK, transport="tcp")
    seen = [set() for _ in devs]

    def kernel(i):
        def run(slab):
            with span("lbm.h2d", server=names[i], bytes=slab.nbytes):
                x = jax.device_put(slab, devs[i])
            out = lbm_step(x)
            del x       # the slab's device copy goes once its step is queued
            seen[i].update(out.devices())
            with span("lbm.d2h", server=names[i], bytes=out.nbytes):
                return np.asarray(out)
        return run

    slabs = split_domain(np.asarray(f0), n_servers)
    bufs = [rt.create_buffer(int(s.nbytes)) for s in slabs]
    evs = [rt.enqueue_write(name, b, s)
           for name, b, s in zip(names, bufs, slabs)]
    step_seconds = []
    for step in range(steps):
        t0 = time.perf_counter()
        for i, b in enumerate(bufs):
            # simulated device time: one read and one write of the slab
            k = rt.enqueue_kernel(names[i], fn=kernel(i), inputs=[b],
                                  outputs=[b], bytes_moved=2 * b.nbytes,
                                  wait_for=[evs[i]], name="lbm_step")
            rt.enqueue_read(names[i], b, wait_for=[k])
        rt.finish()
        slabs = [b.data for b in bufs]
        if step < steps - 1:
            evs = [rt.enqueue_write(name, b, s) for name, b, s in
                   zip(names, bufs, exchange_halos(slabs))]
        step_seconds.append(time.perf_counter() - t0)
    with span("lbm.concatenate"):
        f = np.concatenate([s[:, :, 1:-1] for s in slabs], axis=2)
    return OffloadRun(f=f, devices=[sorted(s, key=lambda d: d.id)
                                    for s in seen],
                      step_seconds=step_seconds, stats=rt.stats())
