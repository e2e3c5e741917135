"""Shared model building blocks: norms, RoPE/M-RoPE, GQA attention, MLPs.

All functions are pure; parameters are plain dict pytrees.  Layer parameters
are stacked along a leading [L] axis by the model assemblers and consumed
through ``jax.lax.scan`` so the HLO stays compact at any depth (essential
for 60-80 layer dry-run compiles).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops

Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# Init helpers
# --------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, dtype, scale: float = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def init_norm(d: int, dtype):
    return jnp.ones((d,), dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def group_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               num_groups: int, eps: float) -> jax.Array:
    """Per-head group norm (RWKV6 output norm).  x: [..., D]."""
    orig = x.shape
    xf = x.astype(jnp.float32).reshape(*orig[:-1], num_groups, -1)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(orig)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# --------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                     / (head_dim // 2))


def yarn_freqs(head_dim: int, theta: float, s) -> Tuple[jax.Array, float]:
    """YaRN's inverse frequencies and cos/sin scale for ``s``
    (:class:`~repro.configs.base.YarnRope`), as HF's
    ``_compute_yarn_parameters`` computes them with ``truncate`` on: the
    correction range is where ``beta_fast`` and ``beta_slow`` rotations
    fit in the original context; below it frequencies are interpolated
    (divided by ``factor``), above it kept, with a linear ramp between."""
    def corr_dim(rot):
        return (head_dim * math.log(s.original_max_positions
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(s.beta_fast)), 0)
    high = min(math.ceil(corr_dim(s.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = _rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1: extrapolate (keep), 0: interpolate
    inv = extra / s.factor * (1.0 - keep) + extra * keep
    return inv, float(s.attention_factor)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [B, H, T, hd]; positions: [B, T] absolute positions; scaling:
    an optional YaRN ``YarnRope``."""
    hd = x.shape[-1]
    if scaling is None:
        freqs, mult = _rope_freqs(hd, theta), None       # [hd/2]
    else:
        freqs, mult = yarn_freqs(hd, theta, scaling)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,T,hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mult is not None:
        cos, sin = cos * mult, sin * mult
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Qwen2-VL M-RoPE: 3 position axes (t, h, w) over head_dim sections.

    x: [B, H, T, hd]; positions3: [3, B, T].  ``sections`` partitions the
    hd/2 frequency dims; section i rotates by positions3[i].
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = _rope_freqs(hd, theta)                       # [hd/2]
    # pick the position source per frequency dim
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=hd // 2)
    # angles[b, t, i] = positions3[sec_id[i], b, t] * freqs[i]
    pos = jnp.take(positions3, sec_id, axis=0)           # [hd/2, B, T]
    angles = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # [B,T,hd/2]
    cos = jnp.cos(angles)[:, None]
    sin = jnp.sin(angles)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA; train full-seq, prefill, and cached decode)
# --------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], cfg.d_model, cfg.q_dim, dt),
        "wk": init_dense(ks[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": init_dense(ks[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": init_dense(ks[3], cfg.q_dim, cfg.d_model, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig):
    b, t, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, t, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def layer_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    """The attention window of a layer of ``kind`` (None: full)."""
    if kind == "window" or cfg.attention_kind == "local":
        return cfg.window
    return None


def layer_rope(cfg: ModelConfig, kind: str):
    """The RoPE scaling of a layer of ``kind``: the configuration's
    ``rope_scaling`` on full layers, plain RoPE on window layers."""
    return cfg.rope_scaling if kind == "full" else None


def _pos_embed(q, k, cfg: ModelConfig, positions, scaling=None):
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta, scaling)
        k = apply_rope(k, positions, cfg.rope_theta, scaling)
    return q, k


def attention_train(p: Params, x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array, *, causal: bool = True,
                    window: Optional[int] = None,
                    rope_scaling=None) -> jax.Array:
    """Full-sequence attention (training / encoder)."""
    b, t, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    q, k = _pos_embed(q, k, cfg, positions, rope_scaling)
    if t >= cfg.attention_chunk_threshold and cfg.attention_impl == "reference":
        o = _chunked_attention(q, k, v, cfg, causal=causal, window=window)
    else:
        o = ops.attention(q, k, v, causal=causal, window=window,
                          impl=cfg.attention_impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_dim)
    return o @ p["wo"]


def _chunked_attention(q, k, v, cfg: ModelConfig, *, causal: bool,
                       window: Optional[int]) -> jax.Array:
    """Q-chunked attention: scores materialize [*, q_chunk, S] at a time.

    Long sequences cannot afford the full [T, S] score tensor in HBM
    (32k x 32k f32 is 4 GB *per head*); scanning over query blocks bounds
    the live score buffer to q_chunk rows.  The Pallas flash kernel is the
    TPU production path; this is the XLA-visible equivalent the dry-run
    lowers, with the same asymptotics.
    """
    b, h, t, d = q.shape
    qc = min(cfg.attention_q_chunk, t)
    n = t // qc
    assert t % qc == 0, (t, qc)
    qs = q.reshape(b, h, n, qc, d).transpose(2, 0, 1, 3, 4)  # [n,B,H,qc,d]

    from ..kernels import ref as _ref

    if window is not None and window + qc < k.shape[2]:
        # local attention: a q chunk starting at p attends only to
        # [p - window + 1, p + qc); slice that KV span instead of scanning
        # the whole sequence (T*W traffic instead of T*S — the §Perf fix
        # for windowed prefill)
        span = window + qc
        s_len = k.shape[2]

        def body(carry, xs):
            qblk, idx = xs
            start = jnp.clip(idx * qc - window, 0, s_len - span)
            kblk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
            o = _ref.attention(qblk, kblk, vblk, causal=causal,
                               window=window, q_offset=idx * qc - start)
            return carry, o

        idxs = jnp.arange(n)
        _, outs = jax.lax.scan(body, 0, (qs, idxs))
        return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d)

    def body(carry, xs):
        qblk, idx = xs
        o = _ref.attention(qblk, k, v, causal=causal, window=window,
                           q_offset=idx * qc)
        return carry, o

    idxs = jnp.arange(n)
    _, outs = jax.lax.scan(body, 0, (qs, idxs))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d)


def attention_prefill(p: Params, x: jax.Array, cfg: ModelConfig,
                      positions: jax.Array, cache_len: int, *,
                      window: Optional[int] = None, rope_scaling=None):
    """Prefill: full-seq attention that also returns the populated KV cache.

    Cache layout: k/v [B, Hkv, S_cache, hd] with the first T slots filled.
    """
    b, t, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    q, k = _pos_embed(q, k, cfg, positions, rope_scaling)
    if t >= cfg.attention_chunk_threshold \
            and cfg.attention_impl == "reference":
        o = _chunked_attention(q, k, v, cfg, causal=True, window=window)
    else:
        o = ops.attention(q, k, v, causal=True, window=window,
                          impl=cfg.attention_impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_dim)
    out = o @ p["wo"]
    pad = cache_len - t
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return out, {"k": k, "v": v}


def attention_decode(p: Params, x: jax.Array, cfg: ModelConfig,
                     cache: Dict[str, jax.Array], pos: jax.Array, *,
                     window: Optional[int] = None, rope_scaling=None):
    """Single-token decode against a KV cache.

    x: [B, 1, D]; cache k/v: [B, Hkv, S, hd]; pos: [] scalar absolute
    position of the new token.  Returns (out [B,1,D], new_cache).
    """
    b, t, _ = x.shape
    q, k_new, v_new = _project_qkv(p, x, cfg)
    bpos = jnp.broadcast_to(pos, (b, t))
    if cfg.mrope:
        p3 = jnp.broadcast_to(pos, (3, b, t))
        q = apply_mrope(q, p3, cfg.rope_theta, cfg.mrope_sections)
        k_new = apply_mrope(k_new, p3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, bpos, cfg.rope_theta, rope_scaling)
        k_new = apply_rope(k_new, bpos, cfg.rope_theta, rope_scaling)
    s = cache["k"].shape[2]
    if window is not None and s == window:
        # ring cache for local attention: slot = pos % window
        slot = jnp.mod(pos, window)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, 0, slot, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, 0, slot, 0))
        # positions of ring slots: slot i holds absolute position
        # pos - ((slot - i) mod window)
        idx = jnp.arange(window)
        kpos = pos - jnp.mod(slot - idx, window)
        valid = kpos >= 0
        g = cfg.num_heads // cfg.num_kv_heads
        qr = q.reshape(b, cfg.num_kv_heads, g, t, cfg.head_dim)
        logits = jnp.einsum("bhgqd,bhsd->bhgqs", qr.astype(jnp.float32),
                            k.astype(jnp.float32)) * cfg.head_dim ** -0.5
        logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqs,bhsd->bhgqd", probs, v.astype(jnp.float32))
        o = o.reshape(b, cfg.num_heads, t, cfg.head_dim)
    else:
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, 0, pos, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, 0, pos, 0))
        o = _decode_attend(q, k, v, cfg, pos, window)
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, t, cfg.q_dim)
    return o @ p["wo"], {"k": k, "v": v}


def _decode_attend(q, k, v, cfg: ModelConfig, pos,
                   window: Optional[int] = None):
    """Masked decode attention: only cache slots <= pos participate (and
    with a ``window``, only the last ``window`` of them)."""
    b = q.shape[0]
    g = cfg.num_heads // cfg.num_kv_heads
    qr = q.reshape(b, cfg.num_kv_heads, g, 1, cfg.head_dim)
    logits = jnp.einsum("bhgqd,bhsd->bhgqs", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * cfg.head_dim ** -0.5
    s = k.shape[2]
    valid = jnp.arange(s) <= pos
    if window is not None:
        valid &= jnp.arange(s) > pos - window
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqs,bhsd->bhgqd", probs, v.astype(jnp.float32))
    return o.reshape(b, cfg.num_heads, 1, cfg.head_dim)


class Ring(NamedTuple):
    """Where a step's window layers write and read their KV ring.

    Each batch row owns a fixed ring of R blocks in the window layers'
    pool (blocks 1 + row * R .. 1 + row * R + R - 1; block 0 is the null
    block): logical block j of the row lives in ring slot j mod R."""
    write: jax.Array     # [B, C] ring block of each new token
    read: jax.Array      # [B, Mw] ring blocks of logical blocks start..
    start: jax.Array     # [B] the logical block in read[:, 0]
    table_block: int     # tokens per block of the full layers' tables


def ring_addresses(block_tables: jax.Array, positions: jax.Array,
                   ring_rows: int, window: int, block_size: int,
                   ring_block_size: int) -> Ring:
    """The step's :class:`Ring` addresses, from each row's index and
    positions alone (the step's signature carries no ring table).

    ring_rows: the blocks of the ring pool (1 + B * R); block_size: the
    full layers' block, ring_block_size: the ring's.  A row whose table
    is empty (block 0: an idle slot) writes and reads the null block.
    The read table is rotated to start at the ring block of the row's
    first query position less ``window`` - 1, and spans R blocks or the
    full layers' table, whichever is shorter: enough for the window and
    the step's new tokens."""
    b, m = block_tables.shape
    r = (ring_rows - 1) // b
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    live = block_tables[:, :1] != 0
    first = 1 + rows * r
    lblk = jnp.clip(positions, 0, m * block_size - 1) // ring_block_size
    write = jnp.where(live, first + lblk % r, 0)
    start = jnp.maximum(positions[:, 0] - window + 1, 0) // ring_block_size
    cols = jnp.arange(min(r, -(-m * block_size // ring_block_size)),
                      dtype=jnp.int32)[None, :]
    read = jnp.where(live, first + (start[:, None] + cols) % r, 0)
    return Ring(write.astype(jnp.int32), read.astype(jnp.int32),
                start.astype(jnp.int32), block_size)


def attention_paged(p: Params, x: jax.Array, cfg: ModelConfig,
                    k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
                    block_tables: jax.Array, positions: jax.Array,
                    last_idx: Optional[jax.Array] = None, *,
                    ring: Optional[Ring] = None, rope_scaling=None):
    """Attention for chunked prefill / decode against a paged KV pool.

    x: [B, C, D] new tokens (decode: C == 1; prefill: C == chunk; mixed
    prefill/decode steps: every row is C wide, with ``last_idx[b] + 1``
    *valid* tokens — a decode row carries 1, a prefilling row carries its
    chunk slice).  k_pool / v_pool: [L, N, Hkv, bs, hd] fixed-size block
    pools of every layer; layer: int32 scalar, this layer's index into
    them.  block_tables: [B, M] int32.  positions: [B, C] absolute
    positions of the new tokens.  last_idx: optional [B] per-row index of
    the last valid token; tokens past it are padding and their K/V are
    routed to the null block (block 0) so they can never touch live cache
    state.

    The new K/V are written into the pool at fixed-stride addresses
    (block = table[pos // bs], slot = pos % bs), one ``hd``-wide row per
    (token, KV head) at (layer, block, head, slot), then the queries
    attend over the request's table — so a batch of *mixed-length* rows
    is one call, no shape compatibility required.  The pools are updated
    in place and never sliced per layer: a row-wise write keeps them in
    the kernels' tiling, where an [Hkv, hd] window would make XLA retile
    the whole pool, and the kernels read the layer by index.

    A window layer passes its ``ring`` (:func:`ring_addresses`): the
    pools are then the window layers' rings, the new K/V go to the ring
    blocks, and the queries read the rotated ring under a window of
    ``cfg.window``.  ``rope_scaling``: the layer's RoPE scaling.
    Returns (out [B, C, D], (k_pool, v_pool)).
    """
    b, c, _ = x.shape
    hkv = cfg.num_kv_heads
    q, k_new, v_new = _project_qkv(p, x, cfg)
    q, k_new = _pos_embed(q, k_new, cfg, positions, rope_scaling)
    bs = k_pool.shape[3]
    m = block_tables.shape[1]
    # clamp: padded prefill positions past the table write into whatever
    # the padding entries point at (the null block) and are never read
    pos = jnp.clip(positions, 0, m * (ring.table_block if ring else bs) - 1)
    if ring is None:
        blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)  # [B, C]
        tables, start, window = block_tables, None, 0
    else:
        blk, tables, start, window = ring.write, ring.read, ring.start, \
            cfg.window
    slot = pos % bs
    if last_idx is not None:
        # per-row token counts: rows in a mixed step share one chunk
        # width, but a decode row must not let its C-1 padding tokens
        # overwrite the real K/V it just wrote at the same position —
        # route every invalid token's write to the null block instead
        valid = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1) \
            <= last_idx[:, None].astype(jnp.int32)
        blk = jnp.where(valid, blk, 0)
        slot = jnp.where(valid, slot, 0)
    # one row per (token, KV head): indices [B*C*Hkv], token-major
    bidx = jnp.repeat(blk.reshape(-1), hkv)
    sidx = jnp.repeat(slot.reshape(-1), hkv)
    hidx = jnp.tile(jnp.arange(hkv, dtype=jnp.int32), b * c)
    kk = jnp.moveaxis(k_new, 1, 2).reshape(b * c * hkv, cfg.head_dim)
    vv = jnp.moveaxis(v_new, 1, 2).reshape(b * c * hkv, cfg.head_dim)
    k_pool = k_pool.at[layer, bidx, hidx, sidx].set(kk.astype(k_pool.dtype))
    v_pool = v_pool.at[layer, bidx, hidx, sidx].set(vv.astype(v_pool.dtype))
    # the platform picks the kernel: Pallas on TPU, the reference elsewhere
    o = ops.paged_attention(q, k_pool, v_pool, tables, pos, layer, start,
                            window=window)
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, c, cfg.q_dim)
    return o @ p["wo"], (k_pool, v_pool)


# --------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# --------------------------------------------------------------------------


def init_cross_attention(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], cfg.d_model, cfg.q_dim, dt),
        "wk": init_dense(ks[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": init_dense(ks[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": init_dense(ks[3], cfg.q_dim, cfg.d_model, dt),
    }


def cross_attention(p: Params, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """x: [B, T, D] decoder states; memory: [B, S, D] encoder output."""
    b, t, _ = x.shape
    s = memory.shape[1]
    q = (x @ p["wq"]).reshape(b, t, cfg.num_heads, cfg.head_dim) \
        .transpose(0, 2, 1, 3)
    k = (memory @ p["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim) \
        .transpose(0, 2, 1, 3)
    v = (memory @ p["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim) \
        .transpose(0, 2, 1, 3)
    if t >= cfg.attention_chunk_threshold \
            and cfg.attention_impl == "reference":
        o = _chunked_attention(q, k, v, cfg, causal=False, window=None)
    else:
        o = ops.attention(q, k, v, causal=False, impl=cfg.attention_impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_dim)
    return o @ p["wo"]


# --------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# --------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    dt = dtype_of(cfg)
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(ks[0], cfg.d_model, f, dt),
        "w_up": init_dense(ks[1], cfg.d_model, f, dt),
        "w_down": init_dense(ks[2], f, cfg.d_model, dt),
    }


def mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    gate = x @ p["w_gate"]
    act = jax.nn.gelu(gate) if cfg.mlp_act == "geglu" else jax.nn.silu(gate)
    return (act * (x @ p["w_up"])) @ p["w_down"]


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    p = {"embed": (jax.random.normal(
        key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = init_dense(jax.random.fold_in(key, 1), cfg.d_model,
                                  cfg.vocab_size, dt)
    return p


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["embed"], tokens, axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # gemma-style scale
    return x


def unembed(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, p["embed"])
    else:
        logits = x @ p["lm_head"]
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE in f32.  logits: [B, T, V]; labels: [B, T] (-1 = ignore)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - picked
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_loss(p: Params, x: jax.Array, labels: jax.Array,
                 cfg: ModelConfig, chunk: int) -> jax.Array:
    """Sequence-chunked vocab loss: bounds the [B, chunk, V] logits buffer.

    The full [B, T, V] logits tensor dominates training memory at large
    vocab (qwen2: 152k).  Chunking the unembed+CE over T keeps peak
    activation memory flat — a beyond-paper memory optimization recorded
    in EXPERIMENTS.md §Perf.
    """
    b, t, d = x.shape
    n = t // chunk

    def body(carry, xs):
        xc, yc = xs   # [B, chunk, D], [B, chunk]
        logits = unembed(p, xc, cfg)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        picked = jnp.take_along_axis(
            lf, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        mask = (yc >= 0).astype(jnp.float32)
        return (carry[0] + jnp.sum((lse - picked) * mask),
                carry[1] + jnp.sum(mask)), None

    xs = (x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3),
          labels.reshape(b, n, chunk).transpose(1, 0, 2))
    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                     xs)
    return total / jnp.maximum(count, 1.0)
