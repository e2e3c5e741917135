"""Blockwise (flash) attention kernel: GQA, causal, optional local window.

Grid: (batch, q_head, Tq/block_q, S/block_k) with the KV axis innermost and
sequential ("arbitrary"), carrying the running max / denominator / output
accumulator in VMEM scratch — the standard TPU online-softmax schedule.
GQA is handled in the index maps: the q-head axis indexes K/V through
``h // group``, so grouped heads reuse the same KV tiles and nothing is
materialized.

Causal and sliding-window masks are position arithmetic on block indices;
fully-masked KV blocks are skipped with ``pl.when`` (no FLOPs, no VMEM
traffic beyond the prefetch).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 block_q: int, block_k: int, kv_blocks: int,
                 q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this q/kv block
    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    # Can this block contribute at all?  (causal: kv must not be entirely
    # in the future; window: kv must not be entirely out of range)
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= (q_start - (k_start + block_k - 1)) < window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...][:, :1]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # [bq, bk]
        correction = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_prev = l_ref[...][:, :1]
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == kv_blocks - 1)
    def _emit():
        denom = l_ref[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "q_offset",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Hq, Tq, D]; k, v: [B, Hkv, S, D].  Returns [B, Hq, Tq, D]."""
    b, hq, tq, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, tq)
    block_k = min(block_k, s)
    assert tq % block_q == 0 and s % block_k == 0, (tq, block_q, s, block_k)
    kv_blocks = s // block_k
    grid = (b, hq, tq // block_q, kv_blocks)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks,
        q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hq, tq, d), q.dtype),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # denominator
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
