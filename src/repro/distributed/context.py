"""Mesh context: logical-axis → mesh-axis rules with divisibility fallback.

Model code stays mesh-agnostic; it calls ``shard_act(x, names)`` which is
a no-op outside a mesh context. The launcher installs a ``MeshContext``
that maps logical names to mesh axes, dropping any axis that does not
divide the corresponding dimension (e.g. batch=1 in long_500k, or 4 query
heads on a 16-way model axis).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical activation/param axis → mesh axes (tuple). Tuned per run.
DEFAULT_RULES = {
    # params
    "stack": (), "embed": ("data",), "vocab": ("model",), "q": ("model",),
    "kvh": ("model",), "mlp": ("model",), "expert": (), "inner": ("model",),
    "hssm": ("model",),
    # activations
    "batch": ("pod", "data"), "seq": (), "heads": ("model",),
    "act_mlp": ("model",), "act_inner": ("model",),
    # KV cache layout (set per cell): 'kv_rep' shards padded kv heads on
    # 'model'; 'seq' shards the cache sequence dim instead
    "kv_heads": ("model",), "kv_seq": (),
    # MoE
    "expert_act": (),
}


# Pure-FSDP strategy: no tensor parallelism — the 'model' axis becomes
# extra data parallelism; weights stay sharded across both axes for
# storage (ZeRO-3) and are gathered per layer. The §Perf hillclimb showed
# this is the right regime for small archs (≤2B) where Megatron TP
# all-reduces dominate the roofline at d_model/16-wide per-device tiles.
FSDP_RULES = {
    "embed": ("data",), "vocab": ("model",), "q": ("model",),
    "kvh": ("model",), "mlp": ("model",), "inner": ("model",),
    "hssm": ("model",), "expert": (),
    "batch": ("pod", "data", "model"), "heads": (), "seq": (),
    "act_mlp": (), "act_inner": (),
    "kv_heads": (), "kv_seq": (), "expert_act": (),
}

STRATEGIES = {"megatron": {}, "fsdp": FSDP_RULES}


class MeshContext:
    def __init__(self, mesh: Mesh, rules: Optional[dict] = None,
                 cache_layout: str = "kv_rep", strategy: str = "megatron"):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        self.rules.update(STRATEGIES.get(strategy, {}))
        self.strategy = strategy
        if rules:
            self.rules.update(rules)
        if cache_layout == "seq":
            self.rules["kv_heads"] = ()
            self.rules["kv_seq"] = ("model",)
        self.cache_layout = cache_layout
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _axes_for(self, name, dim: int):
        if name is None:
            return None
        axes = tuple(a for a in self.rules.get(name, ()) if a in self.axis_sizes)
        if not axes:
            return None
        total = int(np.prod([self.axis_sizes[a] for a in axes]))
        if dim % total != 0:
            # try a prefix of the axes before giving up
            for cut in range(len(axes) - 1, 0, -1):
                sub = axes[:cut]
                t = int(np.prod([self.axis_sizes[a] for a in sub]))
                if dim % t == 0:
                    return sub
            return None
        return axes

    def pspec(self, names: Sequence, shape: Sequence[int]) -> P:
        assert len(names) == len(shape), (names, shape)
        parts = [self._axes_for(n, d) for n, d in zip(names, shape)]
        return P(*parts)

    def sharding(self, names: Sequence, shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(names, shape))

    def tp(self) -> int:
        return self.axis_sizes.get("model", 1)

    def kv_pad_factor(self, n_heads: int, n_kv: int) -> int:
        """Megatron-style KV head replication for TP > n_kv (only when the
        alignment works out; otherwise KV stays replicated)."""
        if self.cache_layout != "kv_rep":
            return 1
        tp = self.tp()
        if tp > n_kv and n_heads % tp == 0 and tp % n_kv == 0:
            return tp // n_kv
        return 1


_tls = threading.local()


def current() -> Optional[MeshContext]:
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshContext]):
    prev = current()
    _tls.ctx = ctx
    try:
        if ctx is not None:
            with ctx.mesh:
                yield ctx
        else:
            yield None
    finally:
        _tls.ctx = prev


def _manual_variant_mesh(mesh: Mesh, manual_axes: frozenset) -> Mesh:
    """Mesh with the given axes typed Manual (for constraints inside a
    partial-manual shard_map region)."""
    types = tuple(jax.sharding.AxisType.Manual if a in manual_axes
                  else jax.sharding.AxisType.Auto for a in mesh.axis_names)
    return Mesh(mesh.devices, mesh.axis_names, axis_types=types)


def shard_act(x: jax.Array, names: Sequence) -> jax.Array:
    """Apply a sharding constraint if a mesh context is installed.

    Inside a partial-manual shard_map region (compressed cross-pod
    gradient sync), values carry varying-manual-axes; the constraint
    must then (a) not mention the manual axes and (b) use a mesh that
    types them Manual."""
    ctx = current()
    if ctx is None:
        return x
    vma = jax.typeof(x).vma
    if vma:
        # inside a partial-manual region: skip the constraint — mixing
        # Manual-typed mesh constraints with the outer Auto mesh tickles
        # an XLA SPMD-partitioner check failure (see EXPERIMENTS.md);
        # propagation from the in_specs shardings covers the auto axes
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, ctx.pspec(names, x.shape)))


def kv_pad(n_heads: int, n_kv: int) -> int:
    ctx = current()
    return ctx.kv_pad_factor(n_heads, n_kv) if ctx is not None else 1
