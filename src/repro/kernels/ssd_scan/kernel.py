"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (B·H, n_chunks) with the chunk dim 'arbitrary' (sequential); the
inter-chunk SSM state [P, N] lives in VMEM scratch across chunk steps —
the recurrence never round-trips HBM, which is the TPU-native version of
the paper's "keep the hot loop on-device" offloading principle.

Per chunk the kernel does four small MXU matmuls (Q×N·N×Q, Q×Q·Q×P,
N×Q·Q×P, Q×N·N×P) and VPU cumsum/exp — chunk length and state width are
chosen MXU-aligned (Q, N, P multiples of 64/128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dA_ref, b_ref, c_ref, y_ref, fin_ref, state_ref, *,
                n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)          # [Q, P]
    dA = dA_ref[...].astype(jnp.float32)        # [Q, 1] (lane-padded)
    Bm = b_ref[...].astype(jnp.float32)         # [Q, N]
    Cm = c_ref[...].astype(jnp.float32)         # [Q, N]
    Q = x.shape[0]

    cs = jnp.cumsum(dA[:, 0])                   # [Q]
    # intra-chunk decay matrix L[i,j] = exp(cs_i - cs_j) for i >= j
    diff = cs[:, None] - cs[None, :]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    L = jnp.where(tril, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    decay_out = jnp.exp(cs)[:, None]            # [Q, 1]
    y += jax.lax.dot_general(Cm * decay_out, state_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: h = h * exp(sum dA) + Σ_j exp(cs_Q - cs_j) B_j ⊗ x_j
    decay_states = jnp.exp(cs[-1] - cs)[:, None]     # [Q, 1]
    new_state = jax.lax.dot_general(x, Bm * decay_states,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    state_ref[...] = state_ref[...] * jnp.exp(cs[-1]) + new_state

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_final():
        fin_ref[...] = state_ref[...]


def ssd_scan(x, dA, Bm, Cm, chunk: int = 128, interpret: bool = False):
    """Head-major SSD scan.

    x: [BH, S, P]; dA: [BH, S]; Bm/Cm: [BH, S, N]
    Returns (y [BH, S, P], final_state [BH, P, N]).
    """
    BH, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    dA2 = dA[..., None]                         # [BH, S, 1]

    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((None, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, Q, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, Q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(x, dA2, Bm, Cm)
    return y, fin
