"""Content-sized cross-pod gradient reduction (paper §5.3 → DCN link).

The paper's ``cl_pocl_content_size`` moves only the meaningful prefix of
a buffer across the slow UE link. The training-framework analogue: the
cross-pod (DCN) gradient all-reduce moves only a top-k packed payload
(values+indices = the "content size") with error feedback accumulating
what was left behind. The intra-pod (ICI) reductions stay exact.

Implemented with partial-manual ``shard_map`` over the 'pod' axis only —
the per-pod body remains auto-sharded over data/model, so the lowered HLO
shows the cross-pod all-gather shrinking to the packed size (visible in
the §Roofline collective term).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk_compress.ref import topk_pack_ref, unpack_ref

Pytree = Any


def _round_block(n: int, block: int) -> int:
    return max(block, ((n + block - 1) // block) * block)


def compressed_psum_leaf(g: jax.Array, err: jax.Array, *, axis: str,
                         k_per_block: int, block: int):
    """One leaf: top-k pack → all-gather(axis) → sum of unpacked payloads.

    Returns (g_synced, new_err). Mean over the axis is applied."""
    n_pods = jax.lax.axis_size(axis)
    shape = g.shape
    n = int(np.prod(shape))
    npad = _round_block(n, block)
    flat = jnp.pad(g.astype(jnp.float32).reshape(-1), (0, npad - n))
    flat = flat + err.astype(jnp.float32)

    vals, idx = topk_pack_ref(flat, k_per_block, block)
    new_err = flat - unpack_ref(vals, idx, block, npad)

    vals_g = jax.lax.all_gather(vals, axis)          # [pods, nb, k]
    idx_g = jax.lax.all_gather(idx, axis)
    dense = jax.vmap(lambda v, i: unpack_ref(v, i, block, npad))(
        vals_g, idx_g).sum(axis=0) / n_pods

    return dense[:n].reshape(shape).astype(g.dtype), new_err.astype(err.dtype)


def init_error_state(grads_like: Pytree, block: int = 1024,
                     dtype=jnp.bfloat16) -> Pytree:
    def f(g):
        n = _round_block(int(np.prod(g.shape)), block)
        return jnp.zeros((n,), dtype)
    return jax.tree.map(f, grads_like)


def compressed_psum_tree(grads: Pytree, err: Pytree, *, axis: str = "pod",
                         k_per_block: int = 32, block: int = 1024):
    """Apply the compressed reduction to every leaf. Must run inside a
    shard_map manual over ``axis``."""
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        gs, es = compressed_psum_leaf(g, e, axis=axis,
                                      k_per_block=k_per_block, block=block)
        out_g.append(gs)
        out_e.append(es)
    return jax.tree.unflatten(tdef, out_g), jax.tree.unflatten(tdef, out_e)


def pod_manual_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map manual ONLY over 'pod'; data/model stay compiler-managed.

    Note: partial-manual shard_map requires check_vma (the default); with
    check_vma=False jax treats the region as fully manual."""
    manual = frozenset({"pod"}) & frozenset(mesh.axis_names)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual)
