"""Paged-attention kernels: block-table KV gathers with fixed strides.

Two entry points over the same pool of fixed-size KV blocks
(serving/kv_cache.py):

  * :func:`paged_attention` — the decode shape: ONE new token per row
    attends over its whole history.
  * :func:`paged_prefill_attention` — the prefill/mixed shape: a
    ``T``-token query tile per row (a chunk of prompt, or a decode row
    padded to the chunk width) attends over the same block-table KV, with
    per-query positions so causal in-chunk masking and mixed
    prefill/decode batches are the *same* mask arithmetic.

    This shape is also the speculative-decoding VERIFIER: a draft-verify
    step feeds each row its pending token plus up to ``spec_len`` drafted
    continuations (``T = spec_len + 1``), and because the kernel already
    produces one output per query position, every drafted token is scored
    in the same branchless pass — per-position logits fall out of the
    unembed, nothing here changes.  Scoring ``T`` tokens costs one
    block-table sweep instead of ``T`` sequential decode calls, which is
    exactly the bandwidth-shaped win the paper gets from removing
    data-dependent serial work: acceptance turns the one-token-per-step
    latency chain into a wide read of KV the pool already holds.

In both, the block table is a scalar-prefetch operand
(``PrefetchScalarGridSpec``), so the index maps translate *logical* block
j of row b into the *physical* pool block ``table[b, j]`` before the
kernel body runs — each grid step's K/V tile is one fixed-stride DMA

    addr = pool_base + (layer * N + table[b, j]) * BLOCK_STRIDE

exactly the Bebop-page addressing discipline applied to generation state.
The pools are every layer's, [L, N, Hkv, bs, D], and the layer is a
third scalar-prefetch operand: the model's layer scan hands the kernels
the pool it carries, never a per-layer slice, which XLA would copy out.
Inside a block there are no data-dependent branches: validity is position
arithmetic (``j*bs + lane <= qpos``) folded into the mask, and the online-
softmax update is the same branchless schedule as flash_attention.py.
Blocks entirely past a row's context are skipped at block granularity with
``pl.when`` — no FLOPs, no VMEM traffic beyond the prefetched table.

Decode grid: (batch, kv_head, logical_block) with the block axis innermost
and sequential, carrying running max / denominator / accumulator in VMEM.
GQA comes for free: queries arrive grouped per KV head ([B, Hkv, g, D]),
so all g grouped heads share each gathered KV tile.  Prefill grid:
(batch, kv_head, q_tile, logical_block) — flash_attention's schedule with
the contiguous KV axis replaced by table-addressed block DMAs, and the g
grouped q heads folded into the q-tile rows so they too share each
gathered KV tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# query-tile heights are multiples of the bf16 sublane tile (16 rows), so
# one choice is legal for f32 and bf16 queries alike
_Q_ALIGN = 16


def _q_tile(rows: int, target: int) -> int:
    """Query-tile height for ``rows`` folded query rows: the largest
    multiple of 16 that is at most ``target`` and divides ``rows``, or
    all of ``rows`` in one tile (a block equal to the array's extent is
    always legal TPU tiling)."""
    if rows <= target:
        return rows
    for bq in range(target // _Q_ALIGN * _Q_ALIGN, 0, -_Q_ALIGN):
        if rows % bq == 0:
            return bq
    return rows


def _paged_kernel(tbl_ref, ctx_ref, lyr_ref, *refs, scale: float,
                  block_size: int, num_blocks: int, window: int):
    if window:
        start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[bi]                       # valid tokens for this row
    if window:
        # table column 0 holds logical block start[bi]; keys below
        # ctx - window are outside the window
        base = (start_ref[bi] + ji) * block_size
        visit = (base < ctx) & (base + block_size > ctx - window)
    else:
        base = ji * block_size              # logical position of the block
        visit = base < ctx

    @pl.when(visit)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [g, d]
        k = k_ref[0, 0].astype(jnp.float32)                # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)                # [bs, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [g,bs]
        # branchless tail mask: arithmetic on positions, not control flow
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos < ctx
        if window:
            keep &= kpos >= ctx - window
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...][:, :1]                         # [g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * correction \
            + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ji == num_blocks - 1)
    def _emit():
        denom = l_ref[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)     # ctx == 0 rows emit zeros
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, ctx_lens: jax.Array,
                    layer: jax.Array, start: Optional[jax.Array] = None, *,
                    window: int = 0, scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """Single-token decode attention through a block table.

    q: [B, Hq, D] (one new token per row); k_pool / v_pool:
    [L, N, Hkv, bs, D], every layer's pool; block_tables: [B, M] int32;
    ctx_lens: [B] int32 (tokens 0..ctx-1 of each row participate); layer:
    int32 scalar, the layer whose blocks are read.  Returns [B, Hq, D].

    ``window`` > 0: only keys ctx - window .. ctx - 1 participate, and
    column j of a row's table holds its logical block ``start[b] + j``
    (``start`` [B] int32, zeros if None): a ring of blocks can be passed
    rotated so that the grid spans only the window.  Blocks wholly
    outside the window are not computed.  ``window`` 0 is full attention
    (``start`` is ignored).
    """
    b, hq, d = q.shape
    _, _, hkv, bs, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    m = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs,
                               num_blocks=m, window=window)
    # with a window, start is a fourth scalar-prefetch operand (the maps
    # ignore it: ``*_``)
    starts = () if not window else (
        jnp.zeros((b,), jnp.int32) if start is None
        else start.astype(jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(starts),
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bi, hi, ji, tbl, ctx, lyr, *_: (bi, hi, 0, 0)),
            # the fixed-stride gather: physical block id from the
            # prefetched table, everything else static
            pl.BlockSpec((None, 1, 1, bs, d),
                         lambda bi, hi, ji, tbl, ctx, lyr, *_:
                         (lyr[0], tbl[bi, ji], hi, 0, 0)),
            pl.BlockSpec((None, 1, 1, bs, d),
                         lambda bi, hi, ji, tbl, ctx, lyr, *_:
                         (lyr[0], tbl[bi, ji], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bi, hi, ji, tbl, ctx, lyr, *_:
                               (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # running max
            pltpu.VMEM((g, 128), jnp.float32),   # denominator
            pltpu.VMEM((g, d), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *starts, qg, k_pool,
      v_pool)
    return out.reshape(b, hq, d)


def _paged_prefill_kernel(tbl_ref, ctx_ref, lyr_ref, *refs, scale: float,
                          block_size: int, num_blocks: int, window: int):
    if window:
        (start_ref, lo_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
         l_ref, acc_ref) = refs
    else:
        qpos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    ji = pl.program_id(3)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[bi]                       # valid tokens for this row
    if window:
        # table column 0 holds logical block start[bi]; no query of the
        # row reads below lo[bi] (its first query position - window + 1)
        base = (start_ref[bi] + ji) * block_size
        visit = (base < ctx) & (base + block_size > lo_ref[bi])
    else:
        base = ji * block_size              # logical position of the block
        visit = base < ctx

    @pl.when(visit)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [tq, d]
        k = k_ref[0, 0].astype(jnp.float32)                # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)                # [bs, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [tq,bs]
        # per-query causal mask: key position s participates for query t
        # iff s <= qpos[t].  Because the chunk's own K/V were scattered
        # into the pool before this call, in-chunk causality is the SAME
        # arithmetic as history masking — no second mask, no branches.
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qp = qpos_ref[0]                                   # [tq, 1] int32
        keep = kpos <= qp
        if window:
            keep &= kpos > qp - window
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...][:, :1]                         # [tq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * correction \
            + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ji == num_blocks - 1)
    def _emit():
        denom = l_ref[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)     # ctx == 0 rows emit zeros
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "window",
                                             "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            qpos: jax.Array, layer: jax.Array,
                            start: Optional[jax.Array] = None, *,
                            window: int = 0,
                            scale: Optional[float] = None,
                            block_q: int = 128,
                            interpret: bool = False) -> jax.Array:
    """Multi-token (chunked-prefill / mixed-step) paged attention.

    q: [B, Hq, T, D] query tiles (T = prefill chunk; decode rows in a
    mixed batch arrive padded to T with repeated positions); k_pool /
    v_pool: [L, N, Hkv, bs, D], every layer's pool; block_tables: [B, M]
    int32; qpos: [B, T] absolute positions of the query tokens (key
    position s participates for query (b, t) iff ``s <= qpos[b, t]``);
    layer: int32 scalar, the layer whose blocks are read.  Returns
    [B, Hq, T, D].

    GQA shares KV tiles the same way decode does: the g grouped q heads
    are folded into the q-tile row axis ([B, Hkv, g*T, D], each row
    carrying its own qpos), so one gathered K/V block feeds every head of
    its KV group instead of being re-fetched g times.

    ``window`` > 0: query (b, t) reads only keys qpos - window + 1 ..
    qpos (HF's sliding mask), and column j of a row's table holds its
    logical block ``start[b] + j`` (``start`` [B] int32, zeros if None),
    as for :func:`paged_attention`; blocks below every query's window
    are not computed.  ``window`` 0 is full attention.
    """
    b, hq, t, d = q.shape
    _, _, hkv, bs, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    m = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    gt = g * t
    qg = q.reshape(b, hkv, gt, d)
    # [B, g*T, 1]: a (block_q, 1) tile of it lands in the same sublane
    # layout as the score rows it masks, and its minor dim equals the
    # array's, which is the only way a width-1 block passes TPU tiling
    qpos_g = jnp.broadcast_to(qpos[:, None, :], (b, g, t)).reshape(b, gt, 1)
    block_q = _q_tile(gt, block_q)
    # block skipping is per row: the whole tile's history ends at the
    # row's max query position
    ctx_lens = jnp.max(qpos, axis=1) + 1

    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               block_size=bs, num_blocks=m, window=window)
    # with a window, start and each row's lowest key position are a
    # fourth and fifth scalar-prefetch operand (the maps ignore them)
    extra = () if not window else (
        jnp.zeros((b,), jnp.int32) if start is None
        else start.astype(jnp.int32),
        (jnp.min(qpos, axis=1) - window + 1).astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(extra),
        grid=(b, hkv, gt // block_q, m),
        in_specs=[
            pl.BlockSpec((1, block_q, 1),
                         lambda bi, hi, qi, ji, tbl, ctx, lyr, *_:
                         (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ji, tbl, ctx, lyr, *_:
                         (bi, hi, qi, 0)),
            # same fixed-stride gather as decode: physical block id from
            # the prefetched table, one DMA per KV head (not per q head)
            pl.BlockSpec((None, 1, 1, bs, d),
                         lambda bi, hi, qi, ji, tbl, ctx, lyr, *_:
                         (lyr[0], tbl[bi, ji], hi, 0, 0)),
            pl.BlockSpec((None, 1, 1, bs, d),
                         lambda bi, hi, qi, ji, tbl, ctx, lyr, *_:
                         (lyr[0], tbl[bi, ji], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ji, tbl, ctx, lyr, *_:
                               (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # denominator
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gt, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *extra,
      qpos_g.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(b, hq, t, d)
