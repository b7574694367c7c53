"""The CFD offload deployment (paper §7.2): a client owns a D2Q9 lattice
and steps it on its servers through ``ClientRuntime``, via the program's
``repro.apps.lbm.run_offloaded``.

One request is one job: ``run_offloaded`` over the client's lattice for
the traffic's ``steps_per_job`` steps, continuing from the last job's
result. The client reads the lattice back at the end of each job, as a
visualisation or output client does.

``correct`` compares one job of the window, drawn from the seed, with the
float64 reference stepped from that job's input, over the whole lattice.
"""
from __future__ import annotations

import functools
import gc
from unittest import mock

import numpy as np

import reference


def control():
    """The control of ``correct``: the reference's step, computed in
    bfloat16 (the precision below the configuration's float32), put in
    place of the program's ``lbm_step``. It has to come out not
    correct."""
    import jax
    import jax.numpy as jnp
    from repro.apps import lbm

    @functools.partial(jax.jit, static_argnames=("tau",))
    def bf16_step(f, tau=0.6):
        g = f.astype(jnp.bfloat16)
        rho = g.sum(axis=0)
        ux = (g[1] + g[5] + g[8] - g[3] - g[6] - g[7]) / rho
        uy = (g[2] + g[5] + g[6] - g[4] - g[7] - g[8]) / rho
        usq = ux * ux + uy * uy
        out = []
        for q, ((cx, cy), w) in enumerate(zip(reference.C, reference.W)):
            cu = cx * ux + cy * uy
            feq = w * rho * (1 + 3 * cu + 4.5 * cu * cu - 1.5 * usq)
            out.append(jnp.roll(g[q] + (feq - g[q]) / tau, (cy, cx),
                                axis=(0, 1)))
        return jnp.stack(out).astype(f.dtype)

    return mock.patch.object(lbm, "lbm_step", bf16_step)


def initial_state(config: dict, seed: int, device):
    """The double shear layer of ``config`` with a seeded relative
    perturbation of ``perturbation`` on every population, made on
    ``device`` in one jitted call and returned on the host."""
    import jax
    import jax.numpy as jnp

    lat = config["lattice"]
    H, W = lat["height"], lat["width"]

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(key, H, W):
        y = jnp.arange(H, dtype=jnp.float32)[:, None] / H
        x = jnp.arange(W, dtype=jnp.float32)[None, :] / W
        s = lat["shear"]
        ux = s["ux"] * jnp.tanh((y - 0.5) * s["sharpness"]) + 0 * x
        uy = s["uy"] * jnp.sin(2 * jnp.pi * x) + 0 * y
        usq = ux * ux + uy * uy
        pops = []
        for (cx, cy), w in zip(reference.C, reference.W):
            cu = cx * ux + cy * uy
            pops.append(w * (1 + 3 * cu + 4.5 * cu * cu - 1.5 * usq))
        f = jnp.stack(pops)
        noise = jax.random.uniform(key, f.shape, jnp.float32, -1.0, 1.0)
        return f * (1 + lat["perturbation"] * noise)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    with jax.default_device(device):
        f = make(key, H, W)
    return np.asarray(f)


class Deployment:
    span = "bench.job"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.apps import lbm
        self.lbm = lbm
        self.config = config
        self.n_servers = config["servers"]
        self.steps = traffic["steps_per_job"]
        lat = config["lattice"]
        self.cells = lat["height"] * lat["width"]
        self.devices = devices
        # server i on the cell's chip i % chips: the layout the check
        # holds every job's outputs to
        self.layout = [devices[i % len(devices)]
                       for i in range(self.n_servers)]
        self.f = initial_state(config, seed, devices[0])
        # warm up every program a job runs: a one-step job compiles the
        # kernel for each server's device at the slab shapes
        self._serve(1)
        self.sample_rng = np.random.default_rng(seed)
        self.jobs = 0
        self.sample = None          # (f_in, f_out) of the drawn job
        self.job_devices = []       # per job: per server, output devices

    def _offload(self, steps: int):
        """One ``run_offloaded`` job with its servers on ``self.layout``.
        The program puts server i on ``jax.local_devices()[i % count]``,
        so it is shown the cell's chips alone as the local devices: on a
        host with more chips than the cell was given, that rule then
        gives the layout too."""
        import jax
        with mock.patch.object(jax, "local_devices",
                               lambda: list(self.devices)):
            return self.lbm.run_offloaded(self.f, self.n_servers, steps)

    def _serve(self, steps: int):
        run = self._offload(steps)
        f_in, self.f = self.f, run.f
        # the job's runtime holds every slab it wrote in reference cycles
        # (some 7 lattices): without a collection per job the host runs
        # out of memory within two or three jobs
        gc.collect()
        return f_in, run

    def serve(self) -> int:
        f_in, run = self._serve(self.steps)
        self.jobs += 1
        self.job_devices.append(run.devices)
        # a sample of one job, drawn uniformly from the seed, is kept
        if self.sample_rng.random() * self.jobs < 1.0:
            self.sample = (f_in, self.f)
        return self.cells * self.steps

    def describe(self) -> list:
        return [f"cfd: {self.jobs} jobs of {self.steps} steps, "
                f"{self.n_servers} servers on chips "
                f"{[d.id for d in self.layout]}"]

    def check(self):
        """The drawn job against the float64 reference, and each job's
        outputs on the devices the layout puts its servers on."""
        expect = [[d.id] for d in self.layout]
        misplaced = sum(
            [[d.id for d in devs] for devs in job] != expect
            for job in self.job_devices)
        f_in, f_out = self.sample
        self.f = None
        err = reference.max_abs_error(f_out, f_in, self.steps,
                                      self.config["tau"])
        limit = self.config["check"]["max_abs_df"]
        failed = int(not err <= limit) + misplaced
        return ({"max_abs_df": {"value": err, "limit": limit},
                 "jobs_off_their_chips": {"value": misplaced,
                                          "limit": 0}},
                failed)
