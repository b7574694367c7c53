"""Paper Fig. 16/17: FluidX3D multi-node scaling (MLUPs/s) and GPU
utilization, 1–3 A6000 servers on 100 Gb fiber.

The benchmark drives the REAL JAX D2Q9 kernel (validated bit-exact
against the monolithic solver) through the PoCL-R runtime at reduced
size for functional correctness, while the timing model uses FluidX3D's
published per-GPU throughput with the paper's 514³ per-GPU domain and
5.2 MB boundary buffers exchanged P2P per step.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import ETH_1G, ETH_100G, GPU_A6000, Row, emit
from repro.apps import lbm
from repro.core import ClientRuntime, ServerSpec

CELLS_PER_GPU = 514 ** 3
GLUPS_PER_GPU = 4.6e9                 # FluidX3D single-A6000 throughput
STEP_S = CELLS_PER_GPU / GLUPS_PER_GPU
HALO_BYTES = 5.2e6                    # paper §7.2
STEPS = 40


def _functional_check() -> float:
    """Run the real kernel through the runtime on 2 servers."""
    f0 = lbm.init_shear(16, 32)
    got = lbm.run_offloaded(f0, 2, 10).f
    ref = f0
    for _ in range(10):
        ref = lbm.lbm_step(ref)
    return float(np.abs(got - np.asarray(ref)).max())


def _scaling(n_servers: int):
    rt = ClientRuntime(servers=[ServerSpec(f"s{i}", [GPU_A6000])
                                for i in range(n_servers)],
                       client_link=ETH_1G, peer_link=ETH_100G,
                       transport="tcp")
    halos = {i: rt.create_buffer(int(HALO_BYTES)) for i in range(n_servers)}
    for i, b in halos.items():
        b.valid_on = {f"s{i}"}
    t0 = rt.clock.now
    prev = {i: None for i in range(n_servers)}
    for step in range(STEPS):
        ks = {}
        for i in range(n_servers):
            deps = [e for e in (prev[i],) if e]
            ks[i] = rt.enqueue_kernel(f"s{i}", fn=None, outputs=[halos[i]],
                                      duration=STEP_S, wait_for=deps,
                                      name="lbm_step")
        if n_servers > 1:
            for i in range(n_servers):
                j = (i + 1) % n_servers
                mig = rt.enqueue_migration(halos[i], f"s{j}",
                                           wait_for=[ks[i]])
                prev[j] = mig
        else:
            prev = {0: ks[0]}
    rt.finish()
    wall = rt.clock.now - t0
    mlups = n_servers * CELLS_PER_GPU * STEPS / wall / 1e6
    util = (STEPS * STEP_S) / wall
    return mlups, util


def run():
    err = _functional_check()
    rows = [Row("fig16_lbm_functional_err", 0.0, f"max_abs_err={err:.2e}")]
    base = None
    for n in (1, 2, 3):
        mlups, util = _scaling(n)
        if base is None:
            base = mlups
        eff = mlups / (base * n)
        rows.append(Row(f"fig16_cfd_{n}node", 0.0,
                        f"mlups={mlups:.0f};scaling_eff={eff:.2f};"
                        f"gpu_util={util:.2f}"))
    return emit(rows)


if __name__ == "__main__":
    run()
