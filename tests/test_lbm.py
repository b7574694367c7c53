"""The CFD case study (paper §7.2) on the CPU at small sizes: the D2Q9
kernel against the float64 numpy reference, and the offload loop
against the monolithic solve."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.apps import lbm
from repro.utils import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 5
TOL = 1e-5          # max|df|, as in chip_smoke.py


@pytest.fixture(scope="module")
def f0():
    """Double shear layer with a seeded perturbation, so every population
    is off equilibrium."""
    f = np.asarray(lbm.init_shear(24, 32))
    noise = np.random.default_rng(0).uniform(-1, 1, f.shape)
    return (f * (1 + 1e-3 * noise)).astype(np.float32)


@pytest.fixture(scope="module")
def monolithic(f0):
    f = f0
    for _ in range(STEPS):
        f = lbm.lbm_step(f)
    return np.asarray(f)


def test_lbm_step_matches_float64_reference(f0, monolithic):
    ref = lbm.reference_steps(f0, STEPS)
    assert np.abs(monolithic - ref).max() <= TOL
    assert np.abs(ref - f0).max() > 100 * TOL      # the state did evolve


@pytest.mark.parametrize("band", [8, 7, 24])
def test_banded_reference_equals_full_reference(f0, monolithic, band):
    full = float(np.abs(monolithic - lbm.reference_steps(f0, STEPS)).max())
    assert lbm.reference_max_error(monolithic, f0, STEPS, band=band) == full


@pytest.mark.parametrize("n_servers", [1, 2, 4])
def test_offloaded_matches_monolithic(f0, monolithic, n_servers):
    run = lbm.run_offloaded(f0, n_servers, STEPS)
    assert run.f.shape == f0.shape
    assert np.abs(run.f - monolithic).max() <= TOL
    assert run.devices == [[jax.devices()[0]]] * n_servers
    assert len(run.step_seconds) == STEPS
    assert run.stats["time"] > 0.0


@pytest.mark.parametrize("h_minor", [False, True])
@pytest.mark.parametrize("shape", [(9, 24, 32), (9, 10, 36)])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_split_domain_equals_gather(shape, n, h_minor):
    """Each slab is the periodic gather of its columns, halos included
    (for n == 1 both halos wrap around the whole lattice), also from a
    lattice with H innermost in memory, the order a TPU hands slabs back
    in."""
    f = np.random.default_rng(1).standard_normal(shape).astype(np.float32)
    if h_minor:
        f = f.transpose(0, 2, 1).copy().transpose(0, 2, 1)
        assert f.strides[1] == f.itemsize
    W_ = shape[2]
    w = W_ // n
    slabs = lbm.split_domain(f, n)
    assert len(slabs) == n
    for i, slab in enumerate(slabs):
        ref = np.take(f, (i * w + np.arange(-1, w + 1)) % W_, axis=2)
        assert slab.dtype == ref.dtype and slab.shape == ref.shape
        assert np.array_equal(slab, ref)
        assert slab.flags.c_contiguous
    assert np.array_equal(
        np.concatenate([s[:, :, 1:-1] for s in slabs], axis=2), f)


def test_split_domain_rejects_uneven_width(f0):
    with pytest.raises(ValueError):
        lbm.split_domain(f0, 3)


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_four_servers_run_on_four_devices(tmp_path):
    """The four-chip path on four virtual CPU devices: one server each."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "cfd_multinode.py"),
         "--nodes", "4", "--steps", "3"],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "outputs from 4 distinct devices" in out.stdout
    assert "distributed == monolithic: OK" in out.stdout


def test_compile_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "cache"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.utils import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                              JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
