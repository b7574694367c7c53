"""Pallas TPU flash attention (forward) with GQA-native K/V indexing.

TPU adaptation of the paper's offload-kernel layer: HBM→VMEM streaming
with online softmax, MXU-aligned tiles, and *block skipping* for causal
and sliding-window masks (the XLA fallback computes masked rectangles;
this kernel doesn't — see models/attention.py docstring).

Grid: (B·H, nq, nk) with the kv dim 'arbitrary' (sequential) so the
running (m, l, acc) state lives in VMEM scratch across kv steps.
K/V BlockSpecs index the *shared* kv head directly (kv_head = h // G),
so GQA streams each K/V tile once per query-head group, not H times.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               q_offset: int, kv_len: int, softcap: Optional[float],
               q_chunk: int, kv_chunk: int, n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = q_offset + qi * q_chunk            # first q position of block
    k_lo = ki * kv_chunk

    # block-level skip: entirely-masked tiles do no work
    needed = (k_lo < kv_len)
    if causal:
        needed &= k_lo <= q_lo + q_chunk - 1
    if window is not None:
        needed &= k_lo + kv_chunk - 1 > q_lo - window

    @pl.when(needed)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale     # [qc, hd]
        k = k_ref[...].astype(jnp.float32)             # [kc, hd]
        v = v_ref[...].astype(jnp.float32)             # [kc, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                          # [qc, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # [qc, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,                 # [B, Sq, H, hd]
    k: jax.Array,                 # [B, Sk, KV, hd]
    v: jax.Array,                 # [B, Sk, KV, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    q_chunk: int = 256,
    kv_chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if kv_len is None:
        kv_len = Sk
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, q_chunk, Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    # [B, S, H, hd] → [B*H, S, hd]; K/V stay at KV heads (GQA-native)
    qr = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, Sq, hd)
    kr = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * KV, Sk, hd)
    vr = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * KV, Sk, hd)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // H
        kvh = (bh % H) // G
        return (b * KV + kvh, ki, 0)

    kernel = functools.partial(
        _fa_kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        q_offset=q_offset, kv_len=kv_len, softcap=logit_softcap,
        q_chunk=q_chunk, kv_chunk=kv_chunk, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, q_chunk, hd), q_map),
            pl.BlockSpec((None, kv_chunk, hd), kv_map),
            pl.BlockSpec((None, kv_chunk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, q_chunk, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_chunk, LANES), jnp.float32),   # running max
            pltpu.VMEM((q_chunk, LANES), jnp.float32),   # running denom
            pltpu.VMEM((q_chunk, hd), jnp.float32),      # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qr, kr, vr)
    return jnp.transpose(out.reshape(B, H, Sq, hd), (0, 2, 1, 3))
