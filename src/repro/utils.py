"""Small shared utilities: pytree arithmetic, dtype policy, shape math."""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. ``$JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, wins
    when set; otherwise the cache lives at ``<repo>/.jax_cache``. The path
    is part of the cache key, so it is fixed. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def storage_barrier(x: Pytree) -> Pytree:
    """Optionally pin values as materialized storage (dry-run only).

    XLA-CPU's excess-precision pass deletes f32→bf16→f32 convert pairs,
    so on the CPU backend the mixed-precision structure of the program
    vanishes from the optimized HLO and the roofline analysis would see
    an all-f32 program. The dry-run sets REPRO_DTYPE_BARRIER=1 to wrap
    down-casts in ``optimization_barrier``, preserving the bf16 storage
    points exactly where a TPU compilation would have them. Real runs
    (flag unset) are unaffected."""
    if os.environ.get("REPRO_DTYPE_BARRIER") == "1":
        return jax.lax.optimization_barrier(x)
    return x


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, a)


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_size(a: Pytree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(a))


def tree_bytes(a: Pytree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(a))


def tree_global_norm(a: Pytree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(a)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def tree_cast(a: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda x: x.astype(dtype), a)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def split_key(key, n: int):
    return list(jax.random.split(key, n))


def pattern_cycles(n_layers: int, pattern_len: int) -> tuple[int, int]:
    """Split n_layers into (n_full_cycles, tail_len) for a repeating pattern."""
    return n_layers // pattern_len, n_layers % pattern_len


def vma_like(x: Pytree, template) -> Pytree:
    """Match a fresh value's varying-manual-axes to a template's.

    Under partial-manual shard_map (pod-manual gradient compression),
    scan carries initialized from constants are 'invariant' while the
    data is pod-'varying'; the VMA checker rejects the mismatch. This
    promotes x when (and only when) the template is varying, and is a
    no-op outside shard_map."""
    vma = jax.typeof(template).vma
    if not vma:
        return x

    def promote(a):
        have = jax.typeof(a).vma
        need = tuple(sorted(vma - have))
        return jax.lax.pcast(a, need, to="varying") if need else a

    return jax.tree.map(promote, x)


def grad_cast(x):
    """Identity whose cotangent is cast back to x's dtype.

    fp32-accumulating einsums (``preferred_element_type=f32``) propagate
    fp32 into their transposed (backward) dots; without a barrier the fp32
    cotangents flow through projections and the residual stream, doubling
    every backward dot, activation store and TP all-reduce. Place this at
    mixed-precision boundaries (loss logits, attention q/k/v)."""
    dtype = x.dtype

    @jax.custom_vjp
    def _f(y):
        return y

    def _fwd(y):
        return y, None

    def _bwd(_, g):
        return (storage_barrier(g.astype(dtype)),)

    _f.defvjp(_fwd, _bwd)
    return _f(x)
