"""Mixture-of-Experts FFN: top-k routing, shared experts, capacity dispatch
for training, dropless dispatch for serving.

Training dispatch (:func:`moe_ffn`): tokens are scattered into a
per-expert [E, C, D] buffer (C = capacity) with positions computed by a
cumulative-sum over the routing assignment, expert FFNs run as batched
einsums over stacked expert weights, and outputs gather back with the
routing weights.  FLOPs scale with top_k (plus shared experts), not with
E.  Tokens beyond capacity are dropped (standard GShard/Switch semantics,
capacity_factor controls slack).

Serving dispatch (:func:`moe_ffn_dropless`): no capacity and no dropped
token.  The layer knows which experts it holds (``MoEConfig.held_first``
and ``held``: one chip's share under expert parallelism); the router
chooses over all ``num_experts``, and the layer adds only its own
experts' part of the output, so a step's rows never compete with each
other, or with padding, for an expert.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Params, dtype_of, init_dense


def init_moe(key, cfg: ModelConfig) -> Params:
    """The router over every expert, the weights of the experts held."""
    m = cfg.moe
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    d, fe, h = cfg.d_model, m.d_expert, m.num_held
    scale = d ** -0.5
    p = {
        "router": init_dense(ks[0], d, m.num_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (h, d, fe),
                                     jnp.float32) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (h, d, fe),
                                   jnp.float32) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (h, fe, d),
                                     jnp.float32) * fe ** -0.5).astype(dt),
    }
    if m.num_shared:
        sk = jax.random.split(ks[4], 3)
        fs = m.d_expert * m.num_shared
        p["shared"] = {
            "w_gate": init_dense(sk[0], d, fs, dt),
            "w_up": init_dense(sk[1], d, fs, dt),
            "w_down": init_dense(sk[2], fs, d, dt),
        }
    return p


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, T, D] -> (out [B, T, D], aux_loss scalar f32)."""
    if cfg.moe.num_held != cfg.moe.num_experts:
        raise NotImplementedError(
            f"{cfg.name}: capacity dispatch needs every expert; a share of "
            f"{cfg.moe.num_held} of {cfg.moe.num_experts} serves through "
            "the paged step (moe_ffn_dropless)")
    if cfg.moe_dispatch == "grouped":
        return moe_ffn_grouped(p, x, cfg)
    return moe_ffn_global(p, x, cfg)


def moe_ffn_grouped(p: Params, x: jax.Array, cfg: ModelConfig
                    ) -> Tuple[jax.Array, jax.Array]:
    """GShard-style grouped dispatch: each batch row is its own dispatch
    group, so the position-in-expert cumsum runs over T (local to a data
    shard) instead of over ALL tokens.  The global-cumsum variant
    (moe_ffn_global) forces an [N*k, E] all-gather across data shards —
    the dominant collective in the baseline dry-run (§Perf, granite cell).
    """
    m = cfg.moe
    b, t, d = x.shape
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)          # [B, T, k]
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)

    density = jnp.mean(jax.nn.one_hot(idx[..., 0], m.num_experts,
                                      dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = jnp.sum(density * mean_prob) * m.num_experts * m.router_aux_coef

    capacity = int(max(1, round(t * m.top_k * m.capacity_factor
                                / m.num_experts)))
    flat_idx = idx.reshape(b, t * m.top_k)                 # [B, T*k]
    onehot = jax.nn.one_hot(flat_idx, m.num_experts, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    keep = pos < capacity                                  # [B, T*k]
    flat_w = weights.reshape(b, t * m.top_k) \
        * keep.astype(weights.dtype)

    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)           # [T*k]
    safe_pos = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[..., None], x[:, tok_idx], 0)  # [B, T*k, D]
    buf = jnp.zeros((b, m.num_experts, capacity, d), x.dtype)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], flat_idx.shape)
    buf = buf.at[bidx, flat_idx, safe_pos].add(contrib, mode="drop")

    gate = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    up = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    act = jax.nn.silu(gate) * up
    out_buf = jnp.einsum("becf,efd->becd", act, p["w_down"])

    expert_out = out_buf[bidx, flat_idx, safe_pos]          # [B, T*k, D]
    expert_out = expert_out * flat_w[..., None].astype(expert_out.dtype)
    out = jnp.zeros((b, t, d), expert_out.dtype) \
        .at[:, tok_idx].add(expert_out)

    if m.num_shared:
        sp = p["shared"]
        g = x @ sp["w_gate"]
        out = out + (jax.nn.silu(g) * (x @ sp["w_up"])) @ sp["w_down"]
    return out, aux


def moe_ffn_global(p: Params, x: jax.Array, cfg: ModelConfig
                   ) -> Tuple[jax.Array, jax.Array]:
    """Global-cumsum dispatch (baseline; kept for §Perf comparison)."""
    m = cfg.moe
    b, t, d = x.shape
    n_tok = b * t
    xt = x.reshape(n_tok, d)

    logits = (xt.astype(jnp.float32) @ p["router"])           # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)              # [N, k]
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], m.num_experts,
                                      dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * mean_prob) * m.num_experts * m.router_aux_coef

    capacity = int(max(1, round(n_tok * m.top_k * m.capacity_factor
                                / m.num_experts)))

    # position of each (token, slot) within its expert
    flat_idx = idx.reshape(-1)                                 # [N*k]
    onehot = jax.nn.one_hot(flat_idx, m.num_experts, dtype=jnp.int32)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)      # [N*k, E]
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)             # [N*k]
    keep = pos < capacity
    flat_w = weights.reshape(-1) * keep.astype(weights.dtype)

    # scatter tokens into the expert buffer [E, C, D]
    tok_idx = jnp.repeat(jnp.arange(n_tok), m.top_k)
    buf = jnp.zeros((m.num_experts, capacity, d), x.dtype)
    safe_pos = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
    buf = buf.at[flat_idx, safe_pos].add(contrib, mode="drop")

    # expert FFN as stacked einsums (swiglu)
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    act = jax.nn.silu(gate) * up
    out_buf = jnp.einsum("ecf,efd->ecd", act, p["w_down"])     # [E, C, D]

    # gather back with routing weights
    expert_out = out_buf[flat_idx, safe_pos]                   # [N*k, D]
    expert_out = expert_out * flat_w[:, None].astype(expert_out.dtype)
    out = jnp.zeros((n_tok, d), expert_out.dtype).at[tok_idx].add(expert_out)

    if m.num_shared:
        sp = p["shared"]
        g = xt @ sp["w_gate"]
        out = out + (jax.nn.silu(g) * (xt @ sp["w_up"])) @ sp["w_down"]

    return out.reshape(b, t, d), aux


def moe_ffn_dropless(p: Params, x: jax.Array, cfg: ModelConfig,
                     valid: jax.Array, layer) -> jax.Array:
    """Serving MoE with no capacity: every routed token reaches its expert.

    x: [B, T, D]; valid: [B, T] bool, False for padding tokens, which
    route nowhere (they cost no expert work and change no row).  The
    router takes the top-k of the softmax over all ``num_experts``
    logits and renormalises those k weights; the (token, expert) pairs
    whose expert this layer holds are sorted by expert, one grouped
    matmul per projection runs over the held experts
    (``jax.lax.ragged_dot``), and the weighted results are added back to
    their tokens.  The shared experts, if any, run on every token.

    ``p``'s expert weights are every layer's, stacked [L * held, ...];
    ``layer`` is the one whose groups are filled (the others stay empty,
    so the matmul reads only this layer's experts and no per-layer slice
    of the weights is copied out).
    Returns out [B, T, D].
    """
    m = cfg.moe
    b, t, d = x.shape
    n, k, h = b * t, m.top_k, m.num_held
    xt = x.reshape(n, d)
    with jax.named_scope("moe_dispatch"):
        logits = xt.astype(jnp.float32) @ p["router"]             # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, k)                    # [N, k]
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
        local = idx - m.held_first
        mine = (local >= 0) & (local < h) & valid.reshape(n, 1)
        # pairs of experts not held here (and padding) sort last, past
        # every group, and are neither computed nor added
        expert = jnp.where(mine, local, h).reshape(-1)            # [N*k]
        order = jnp.argsort(expert, stable=True)
        tok = order // k
        sizes = jnp.sum(jax.nn.one_hot(expert, h, dtype=jnp.int32), axis=0)
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((p["w_gate"].shape[0],), jnp.int32), sizes,
            (layer * h,))
        keep = mine.reshape(-1)[order]
        w = weights.reshape(-1)[order]
        xs = xt[tok]                                              # [N*k, D]
    gate = jax.lax.ragged_dot(xs, p["w_gate"], sizes)
    up = jax.lax.ragged_dot(xs, p["w_up"], sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["w_down"], sizes)
    with jax.named_scope("moe_combine"):
        y = jnp.where(keep[:, None], y.astype(jnp.float32) * w[:, None],
                      0.0)
        out = jnp.zeros((n, d), jnp.float32).at[tok].add(y).astype(x.dtype)
    if m.num_shared:
        sp = p["shared"]
        g = xt @ sp["w_gate"]
        out = out + (jax.nn.silu(g) * (xt @ sp["w_up"])) @ sp["w_down"]
    return out.reshape(b, t, d)
