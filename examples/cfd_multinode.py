"""Multi-node CFD (paper §7.2, FluidX3D) — runnable demo.

Runs the real JAX D2Q9 lattice-Boltzmann solver domain-decomposed over
servers of ``ClientRuntime``, each stepping its slab on its own JAX device
(servers share devices round-robin when there are fewer), and verifies the
distributed result against the monolithic solver. Device utilization is
read from the runtime's simulated timeline.

  PYTHONPATH=src python examples/cfd_multinode.py [--nodes 2] [--steps 20]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np               # noqa: E402

from repro.apps import lbm       # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    H, W = args.size // 2, args.size
    f0 = lbm.init_shear(H, W)
    run = lbm.run_offloaded(f0, args.nodes, args.steps)

    ref = f0
    for _ in range(args.steps):
        ref = lbm.lbm_step(ref)
    err = float(np.abs(run.f - np.asarray(ref)).max())
    print(f"{args.nodes} nodes × {args.steps} steps on a "
          f"{H}×{W} lattice: max|Δ| vs monolithic = {err:.2e}")
    distinct = {d for devs in run.devices for d in devs}
    print(f"  outputs from {len(distinct)} distinct devices: "
          f"{[[str(d) for d in devs] for devs in run.devices]}")
    horizon = run.stats["time"]
    for k, busy in run.stats["device_busy"].items():
        print(f"  {k}: simulated utilization {busy/horizon:.1%}")
    if not err <= 1e-5:
        sys.exit("distributed != monolithic")
    print("distributed == monolithic: OK")


if __name__ == "__main__":
    main()
