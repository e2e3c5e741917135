"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).  They are
also the fallback implementation models use when no TPU is present — the
dry-run lowers these, which is what XLA would fuse on TPU anyway.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Bebop page decode (the paper's technique, §4.4 -> TPU)
# --------------------------------------------------------------------------


def _compose_le(sl: jax.Array, n: int, count: int, width: int, dtype
                ) -> jax.Array:
    """Little-endian byte compose via shift-or.

    Bit-identical to ``bitcast_convert_type`` on the reshaped byte groups,
    but XLA:CPU vectorizes the shift-or form ~2x better than the
    narrow-to-wide bitcast, and it widens into the output dtype in the same
    pass (no separate ``astype`` sweep).
    """
    b = sl.reshape(n, count, width).astype(dtype)
    out = b[..., 0]
    for i in range(1, width):
        out = out | (b[..., i] << (8 * i))
    return out


def bytes_to_u32(pages: jax.Array, offset: int, count: int) -> jax.Array:
    """[N, stride] u8 -> [N, count] u32 starting at byte ``offset`` (LE)."""
    n = pages.shape[0]
    sl = jax.lax.slice(pages, (0, offset), (n, offset + 4 * count))
    return _compose_le(sl, n, count, 4, jnp.uint32)


def bytes_to_i32(pages: jax.Array, offset: int, count: int) -> jax.Array:
    # Composing directly in int32 gives the same two's-complement bits as
    # bitcast-then-astype without the extra pass.
    n = pages.shape[0]
    sl = jax.lax.slice(pages, (0, offset), (n, offset + 4 * count))
    return _compose_le(sl, n, count, 4, jnp.int32)


def bytes_to_u16(pages: jax.Array, offset: int, count: int) -> jax.Array:
    n = pages.shape[0]
    sl = jax.lax.slice(pages, (0, offset), (n, offset + 2 * count))
    return _compose_le(sl, n, count, 2, jnp.uint16)


def bytes_to_f32(pages: jax.Array, offset: int, count: int) -> jax.Array:
    return jax.lax.bitcast_convert_type(
        bytes_to_u32(pages, offset, count), jnp.float32)


def bytes_to_bf16(pages: jax.Array, offset: int, count: int,
                  out_dtype=jnp.float32) -> jax.Array:
    """bfloat16 wire bits -> float32 (or bfloat16) values."""
    u16 = bytes_to_u16(pages, offset, count)
    f32 = jax.lax.bitcast_convert_type(
        u16.astype(jnp.uint32) << 16, jnp.float32)
    return f32.astype(out_dtype)


def bytes_to_u8(pages: jax.Array, offset: int, count: int) -> jax.Array:
    n = pages.shape[0]
    return jax.lax.slice(pages, (0, offset), (n, offset + count))


def bytes_to_f16(pages: jax.Array, offset: int, count: int) -> jax.Array:
    u16 = bytes_to_u16(pages, offset, count)
    return jax.lax.bitcast_convert_type(u16, jnp.float16).astype(jnp.float32)


DECODERS = {
    "uint32": bytes_to_u32,
    "int32": bytes_to_i32,
    "uint16": bytes_to_u16,
    "float32": bytes_to_f32,
    "bfloat16": bytes_to_bf16,
    "float16": bytes_to_f16,
    "uint8": bytes_to_u8,
    "byte": bytes_to_u8,
    "bool": bytes_to_u8,
}


# --------------------------------------------------------------------------
# Attention (GQA, causal, optional local window)
# --------------------------------------------------------------------------


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              q_offset: int = 0) -> jax.Array:
    """Reference softmax attention.

    q: [B, Hq, Tq, D];  k, v: [B, Hkv, S, D] with Hq % Hkv == 0 (GQA).
    ``q_offset``: absolute position of q[0] (decode steps attend into a
    longer KV history).  ``window``: keys with (qpos - kpos) >= window are
    masked (sliding-window / local attention).
    """
    b, hq, tq, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qr = q.reshape(b, hkv, g, tq, d)
    # The named scope marks every op that touches [*, Tq, S] score tensors;
    # the HLO analyzer uses it (metadata survives SPMD partitioning) to
    # compute the flash-kernel-adjusted memory term: a fused attention
    # kernel keeps all of this in VMEM.
    with jax.named_scope("attn_scores"):
        logits = jnp.einsum("bhgtd,bhsd->bhgts", qr.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        qpos = jnp.arange(tq)[:, None] + q_offset
        kpos = jnp.arange(s)[None, :]
        mask = jnp.ones((tq, s), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (with windows): softmax of -inf -> nan
        probs = jnp.where(jnp.isnan(probs), 0.0, probs)
        out = jnp.einsum("bhgts,bhsd->bhgtd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, tq, d).astype(q.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, qpos: jax.Array,
                    layer: jax.Array, start: Optional[jax.Array] = None, *,
                    window: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Reference paged attention over a block-pooled KV cache.

    q: [B, Hq, T, D] new-token queries (decode: T == 1; chunked prefill:
    T == chunk).  k_pool / v_pool: [L, N, Hkv, bs, D] every layer's
    fixed-size block pools.  block_tables: [B, M] int32 physical block ids
    (logical block j of row b lives at ``block_tables[b, j]``).  qpos:
    [B, T] absolute positions of the query tokens; key position s
    participates for query (b, t) iff ``s <= qpos[b, t]`` (causal over the
    request's own history).  layer: int32 scalar, the layer read.

    Semantically identical to :func:`attention` against the contiguous
    cache the table describes; the Pallas kernels gather blocks by table
    lookup instead of materializing the [B, M*bs, ...] view.  This single
    oracle covers both kernel shapes: ``paged_attention`` (T == 1 decode)
    and ``paged_prefill_attention`` (T > 1 chunked prefill / mixed
    prefill+decode steps, where decode rows arrive padded to the chunk
    width with repeated qpos — the per-query mask makes padding rows
    harmless duplicates, never new information).

    ``window`` > 0: key s participates only if also ``s > qpos[b, t] -
    window`` (HF's sliding mask), and table column j holds logical block
    ``start[b] + j`` (``start`` [B], zeros if None): a rotated ring.
    """
    k_pool, v_pool = k_pool[layer], v_pool[layer]
    b, hq, t, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    m = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # gather: [B, M, Hkv, bs, D] -> [B, Hkv, M*bs, D] (logical order)
    k = jnp.moveaxis(k_pool[block_tables], 2, 1).reshape(b, hkv, m * bs, d)
    v = jnp.moveaxis(v_pool[block_tables], 2, 1).reshape(b, hkv, m * bs, d)
    qr = q.reshape(b, hkv, g, t, d)
    logits = jnp.einsum("bhgtd,bhsd->bhgts", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kpos = jnp.broadcast_to(jnp.arange(m * bs), (b, m * bs))
    if window and start is not None:
        kpos = kpos + start[:, None] * bs
    mask = kpos[:, None, :] <= qpos[:, :, None]             # [B, T, S]
    if window:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, t, d).astype(q.dtype)


# --------------------------------------------------------------------------
# RWKV6 (Finch) WKV recurrence with data-dependent decay
# --------------------------------------------------------------------------


def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
          u: jax.Array, initial_state: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """Reference WKV6.

    r, k, w: [B, H, T, K];  v: [B, H, T, V];  u: [H, K]
    w are per-step decay factors in (0, 1] (already exp(-exp(...))'d).
    Returns (out [B, H, T, V], final_state [B, H, K, V]).

        o_t = (S_{t-1} + diag(u) k_t v_t^T)^T r_t
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    bb, hh, tt, kk = r.shape
    vv = v.shape[-1]
    f32 = jnp.float32
    if initial_state is None:
        initial_state = jnp.zeros((bb, hh, kk, vv), f32)

    def step(S, inputs):
        r_t, k_t, v_t, w_t = inputs  # [B,H,K],[B,H,K],[B,H,V],[B,H,K]
        kv = k_t[..., :, None] * v_t[..., None, :]          # [B,H,K,V]
        att = S + u[None, :, :, None] * kv                  # [B,H,K,V]
        o_t = jnp.einsum("bhk,bhkv->bhv", r_t, att)
        S = w_t[..., :, None] * S + kv
        return S, o_t

    xs = (jnp.moveaxis(r, 2, 0).astype(f32), jnp.moveaxis(k, 2, 0).astype(f32),
          jnp.moveaxis(v, 2, 0).astype(f32), jnp.moveaxis(w, 2, 0).astype(f32))
    final, outs = jax.lax.scan(step, initial_state.astype(f32), xs)
    out = jnp.moveaxis(outs, 0, 2).astype(v.dtype)
    return out, final


def rwkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                  u: jax.Array, *, chunk: int = 32,
                  initial_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Chunked-parallel WKV6 (flash-linear-attention style).

    Mathematically identical to :func:`rwkv6` but restructured so the
    [K, V] state is read/written once per CHUNK instead of once per step,
    and the intra-chunk work becomes three matmuls — the schedule the
    Pallas kernel implements in VMEM, expressed in pure JAX so the dry-run
    HLO reflects it.  This is the §Perf memory-term optimization for the
    rwkv6 cells (state traffic drops by the chunk factor; FLOPs move onto
    the MXU).

    Numerics: within-chunk decays are factored as
    q'_t = r_t * exp(logA_{t-1}),  k'_s = k_s * exp(-logA_s); chunk sizes
    <= 64 keep the exponents inside f32 range for RWKV6's decay
    parameterization (validated against the sequential oracle in tests).
    """
    bb, hh, tt, kk = r.shape
    vv = v.shape[-1]
    f32 = jnp.float32
    chunk = min(chunk, tt)
    assert tt % chunk == 0, (tt, chunk)
    n_chunks = tt // chunk
    if initial_state is None:
        initial_state = jnp.zeros((bb, hh, kk, vv), f32)

    def split(x):
        # [B,H,T,D] -> [n, B,H,C,D]
        return jnp.moveaxis(
            x.reshape(bb, hh, n_chunks, chunk, -1), 2, 0).astype(f32)

    rs, ks, vs, ws = split(r), split(k), split(v), split(w)
    uu = u.astype(f32)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)  # strict s < t

    def per_chunk(S, inputs):
        rc, kc, vc, wc = inputs                      # [B,H,C,K] / [B,H,C,V]
        lw = jnp.log(wc)
        logA = jnp.cumsum(lw, axis=2)                # inclusive  [B,H,C,K]
        logA_excl = logA - lw
        qp = rc * jnp.exp(logA_excl)
        kp = kc * jnp.exp(-logA)
        # intra-chunk attention-like term (strictly causal)
        P = jnp.einsum("bhtk,bhsk->bhts", qp, kp)
        P = jnp.where(mask[None, None], P, 0.0)
        o = jnp.einsum("bhts,bhsv->bhtv", P, vc)
        # bonus diagonal
        D = jnp.einsum("bhtk,k->bht", rc * kc,
                       jnp.ones((kk,), f32)) if False else \
            jnp.sum(rc * uu[None, :, None, :] * kc, axis=-1)
        o = o + D[..., None] * vc
        # inter-chunk: incoming state
        o = o + jnp.einsum("bhtk,bhkv->bhtv", qp, S)
        # state update
        A_c = jnp.exp(logA[:, :, -1])                # [B,H,K]
        S = A_c[..., :, None] * S + jnp.einsum(
            "bhsk,bhsv->bhkv", kp * A_c[..., None, :], vc)
        return S, o

    final, outs = jax.lax.scan(per_chunk, initial_state.astype(f32),
                               (rs, ks, vs, ws))
    out = jnp.moveaxis(outs, 0, 2).reshape(bb, hh, tt, vv).astype(v.dtype)
    return out, final


# --------------------------------------------------------------------------
# RG-LRU (RecurrentGemma) diagonal recurrence
# --------------------------------------------------------------------------


def rglru(x: jax.Array, a: jax.Array,
          initial_state: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """Reference RG-LRU recurrence.

    x: [B, T, D] gated+scaled input (sqrt(1-a^2) * i_t * x_t precomputed),
    a: [B, T, D] per-step decay in (0, 1].
    Returns (h [B, T, D], final_state [B, D]).   h_t = a_t h_{t-1} + x_t
    """
    bb, tt, dd = x.shape
    f32 = jnp.float32
    if initial_state is None:
        initial_state = jnp.zeros((bb, dd), f32)

    def step(h, inputs):
        x_t, a_t = inputs
        h = a_t * h + x_t
        return h, h

    xs = (jnp.moveaxis(x, 1, 0).astype(f32), jnp.moveaxis(a, 1, 0).astype(f32))
    final, hs = jax.lax.scan(step, initial_state.astype(f32), xs)
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), final
