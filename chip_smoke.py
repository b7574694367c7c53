#!/usr/bin/env python3
"""Bring-up check on a TPU: the offloaded CFD case study (paper §7.2)
through ``ClientRuntime`` at a real lattice size.

Default, one chip: two servers step an f32 D2Q9 lattice of 8192×8192
cells (half the paper's 514³-cell per-GPU domain) through the runtime.
The result must match the monolithic ``lbm_step`` on the chip, and that
must match a float64 numpy reference, both to max|Δf| ≤ 1e-5.

``--four-chips``: four servers, one per chip, on the same lattice, checked
against the monolithic step on the first chip; the outputs must come from
four distinct devices. Nothing else runs.

The script fails, printing no result line, where JAX finds no TPU. Its
last line of output is ``{"ok": true, "device": {...}}``.

  python3 chip_smoke.py [--four-chips]
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.apps import lbm                      # noqa: E402
from repro.utils import enable_compile_cache    # noqa: E402

H = W = 8192
STEPS = 5
TOL = 1e-5
SEED = 0


def fail(msg: str):
    sys.exit(f"chip_smoke: FAIL: {msg}")


def timed_steps(f, device, steps: int):
    """Monolithic ``lbm_step`` on one device; per-step wall seconds end in
    ``block_until_ready``. Returns the host copy and the step times."""
    f = jax.device_put(f, device).block_until_ready()
    secs = []
    for _ in range(steps):
        t0 = time.perf_counter()
        f = lbm.lbm_step(f).block_until_ready()
        secs.append(time.perf_counter() - t0)
    return np.asarray(f), secs


def compile_seconds(shape, device) -> float:
    """First call of ``lbm_step`` at ``shape`` on ``device``: compile time
    plus one step."""
    x = jnp.zeros(shape, jnp.float32, device=device)
    t0 = time.perf_counter()
    lbm.lbm_step(x).block_until_ready()
    return time.perf_counter() - t0


def fmt(secs) -> str:
    return "[" + ", ".join(f"{s:.4f}" for s in secs) + "]"


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-server path, one server per "
                         "chip, against the monolithic step")
    args = ap.parse_args()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"JAX finds no TPU (platform {platform!r}); this script "
             f"checks the chip and has no CPU fallback")
    n_servers = 4 if args.four_chips else 2
    if args.four_chips and len(devices) < 4:
        fail(f"--four-chips needs 4 chips, JAX finds {len(devices)}")
    print(f"cache: {enable_compile_cache()}")
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x{len(devices)}")
    print(f"lattice: f32 [9, {H}, {W}] = {H * W} cells, "
          f"{9 * H * W * 4 / 2**30:.2f} GiB; {n_servers} servers, "
          f"{STEPS} steps")

    t0 = time.perf_counter()
    key = jax.random.key(SEED)
    f0 = lbm.init_shear(H, W)
    # seeded perturbation: every population differs from equilibrium
    f0 = f0 * (1 + 1e-3 * jax.random.uniform(key, f0.shape, minval=-1.0))
    f0 = np.asarray(f0)
    print(f"setup s (initial state to host): {time.perf_counter() - t0:.3f}")

    slab = (9, H, W // n_servers + 2)
    local = jax.local_devices()
    server_devs = {local[i % len(local)] for i in range(n_servers)}
    for d in sorted(server_devs, key=lambda d: d.id):
        print(f"compile s, slab {slab} on {d}: "
              f"{compile_seconds(slab, d):.3f}")
    print(f"compile s, monolithic on {devices[0]}: "
          f"{compile_seconds(f0.shape, devices[0]):.3f}")

    run = lbm.run_offloaded(f0, n_servers, STEPS)
    print(f"offloaded wall s per step on {kind} (ended by the runtime's "
          f"host copy): {fmt(run.step_seconds)}")
    print(f"offloaded simulated clock s (modeled links): "
          f"{run.stats['time']:.6f}")
    print(f"server output devices: "
          f"{[[str(d) for d in devs] for devs in run.devices]}")
    seen = {d for devs in run.devices for d in devs}
    if any(d.platform != "tpu" for d in seen):
        fail(f"a server computed off the TPU: {seen}")
    if args.four_chips and len(seen) != 4:
        fail(f"outputs came from {len(seen)} distinct devices, not 4")

    mono, secs = timed_steps(f0, devices[0], STEPS)
    print(f"monolithic wall s per step on {kind} (block_until_ready): "
          f"{fmt(secs)}")
    failed = []
    err = float(np.abs(run.f - mono).max())
    print(f"max|df| offloaded vs monolithic: {err:.3e} (bound {TOL})")
    if not err <= TOL:
        failed.append("offloaded vs monolithic")
    if not args.four_chips:
        t0 = time.perf_counter()
        err = lbm.reference_max_error(mono, f0, STEPS)
        print(f"max|df| monolithic vs float64 numpy: {err:.3e} "
              f"(bound {TOL}; {time.perf_counter() - t0:.1f} s on host)")
        if not err <= TOL:
            failed.append("monolithic vs float64 reference")
    if failed:
        fail(f"{', '.join(failed)} over the bound")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
