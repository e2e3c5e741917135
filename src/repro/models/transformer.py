"""Model assembly: decoder-only LM (dense / MoE / RWKV / VLM), hybrid
(RecurrentGemma), encoder-decoder (Seamless backbone).

Layers are stacked along a leading [L] axis and consumed via
``jax.lax.scan`` — the HLO is depth-independent, which keeps 80-layer
dry-run compiles tractable, and remat applies cleanly to the scanned body.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import moe as M
from . import rglru as R
from . import rwkv6 as W

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


def _gather_layer(layer_p, cfg: ModelConfig):
    """FSDP per-layer gather: constrain the scan's per-layer parameter
    slice to be replicated.  With the stacked [L, ...] params sharded over
    the model axis, this turns into ONE layer's all-gather per scan
    iteration — bounded transient memory — instead of SPMD hoisting a
    whole-stack gather out of the loop (observed in the dry-run HLO)."""
    if not cfg.fsdp_per_layer_gather:
        return layer_p
    from jax.sharding import PartitionSpec as P
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(
            x, P(*([None] * x.ndim))), layer_p)


def _by_period(tree, p: int):
    """Stacked [L, ...] layer leaves as [L // p, p, ...]: a scan over
    periods of the layer pattern (unchanged for a period of one)."""
    if p == 1:
        return tree
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // p, p) + a.shape[1:]), tree)


def _member(tree, p: int, i: int):
    """Layer ``i`` of a period's stacked leaves."""
    return tree if p == 1 else jax.tree.map(lambda a: a[i], tree)


def _stack_period(items, p: int):
    """Per-layer outputs of one period, stacked as the period's [p, ...]."""
    return items[0] if p == 1 else jax.tree.map(
        lambda *a: jnp.stack(a), *items)


def _unperiod(tree, p: int):
    """[L // p, p, ...] scan outputs back to stacked [L, ...] layers."""
    if p == 1:
        return tree
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), tree)


# ==========================================================================
# Homogeneous decoder layer (dense / moe / vlm / rwkv)
# ==========================================================================


def init_decoder_layer(key, cfg: ModelConfig) -> Params:
    if cfg.rwkv:
        return W.init_rwkv_block(key, cfg)
    ks = jax.random.split(key, 2)
    dt = L.dtype_of(cfg)
    p: Params = {
        "ln1": L.init_norm(cfg.d_model, dt),
        "ln2": L.init_norm(cfg.d_model, dt),
        "attn": L.init_attention(ks[0], cfg),
    }
    if cfg.moe is not None:
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def decoder_layer_train(p: Params, x: jax.Array, cfg: ModelConfig,
                        positions, kind: str = "full"
                        ) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss).  ``kind``: the layer's kind in the period
    (``cfg.period``), which picks its window and its RoPE."""
    if cfg.rwkv:
        x, _ = W.rwkv_block(p, x, cfg)
        return x, jnp.float32(0)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + L.attention_train(p["attn"], h, cfg, positions,
                              window=L.layer_window(cfg, kind),
                              rope_scaling=L.layer_rope(cfg, kind))
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, aux = M.moe_ffn(p["moe"], h, cfg)
        return x + y, aux
    return x + L.mlp(p["mlp"], h, cfg), jnp.float32(0)


def decoder_layer_prefill(p: Params, x, cfg: ModelConfig, positions,
                          cache_len: int, kind: str = "full"):
    if cfg.rwkv:
        x, state = W.rwkv_block(p, x, cfg)
        return x, state
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    att, kv = L.attention_prefill(p["attn"], h, cfg, positions, cache_len,
                                  window=L.layer_window(cfg, kind),
                                  rope_scaling=L.layer_rope(cfg, kind))
    x = x + att
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _ = M.moe_ffn(p["moe"], h, cfg)
        x = x + y
    else:
        x = x + L.mlp(p["mlp"], h, cfg)
    return x, kv


def decoder_layer_paged(p: Params, x, cfg: ModelConfig, k_pool, v_pool,
                        layer, block_tables, positions, last_idx=None, *,
                        kind: str = "full", ring=None, valid=None,
                        experts=None, depth=None):
    """One decoder layer against the stacked paged KV pool (prefill chunk,
    decode, or a mixed prefill/decode step with per-row token counts);
    ``layer`` indexes this layer's blocks in the pool.  A window layer
    (``kind``) gets the window layers' ring pool and the step's ``ring``
    addresses.  The MoE layer is the dropless one: ``valid`` [B, C] masks
    the padding tokens out of it, and ``experts`` are every layer's expert
    weights stacked, of which layer ``depth``'s are read."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    att, pools = L.attention_paged(p["attn"], h, cfg, k_pool, v_pool, layer,
                                   block_tables, positions,
                                   last_idx=last_idx, ring=ring,
                                   rope_scaling=L.layer_rope(cfg, kind))
    x = x + att
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        x = x + M.moe_ffn_dropless(dict(p["moe"], **experts), h, cfg,
                                   valid, depth)
    else:
        x = x + L.mlp(p["mlp"], h, cfg)
    return x, pools


def decoder_layer_decode(p: Params, x, cfg: ModelConfig, cache, pos,
                         kind: str = "full"):
    if cfg.rwkv:
        x, state = W.rwkv_block(p, x, cfg, state=cache)
        return x, state
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    att, kv = L.attention_decode(p["attn"], h, cfg, cache, pos,
                                 window=L.layer_window(cfg, kind),
                                 rope_scaling=L.layer_rope(cfg, kind))
    x = x + att
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _ = M.moe_ffn(p["moe"], h, cfg)
        x = x + y
    else:
        x = x + L.mlp(p["mlp"], h, cfg)
    return x, kv


# ==========================================================================
# DecoderLM
# ==========================================================================


class DecoderLM:
    """Decoder-only LM over scanned homogeneous layers."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- params ---------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_layers = jax.random.split(key)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        stacked = jax.vmap(lambda k: init_decoder_layer(k, cfg))(layer_keys)
        p = L.init_embedding(k_emb, cfg)
        p["layers"] = stacked
        p["final_norm"] = L.init_norm(cfg.d_model, L.dtype_of(cfg))
        return p

    # -- shared input handling ---------------------------------------------------
    def _inputs(self, params: Params, batch: Batch):
        cfg = self.cfg
        if cfg.input_kind == "embeddings":
            x = batch["embeds"].astype(L.dtype_of(cfg))
            positions = batch["positions"]  # [3, B, T] (M-RoPE)
        else:
            tokens = batch["tokens"]
            x = L.embed(params, tokens, cfg)
            b, t = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
            if cfg.mrope:
                positions = jnp.broadcast_to(positions, (3, b, t))
        return x, positions

    # -- train -----------------------------------------------------------------
    def forward(self, params: Params, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x, positions = self._inputs(params, batch)
        kinds = cfg.period
        n = len(kinds)

        def body(carry, layer_p):
            x, aux = carry
            for i, kind in enumerate(kinds):
                lp = _gather_layer(_member(layer_p, n, i), cfg)
                x, a = decoder_layer_train(lp, x, cfg, positions, kind)
                aux = aux + a
            return (x, aux), None

        body_fn = body
        if cfg.remat == "full":
            body_fn = jax.checkpoint(body,
                                     policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0)),
                                   _by_period(params["layers"], n))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux

    def loss(self, params: Params, batch: Batch) -> jax.Array:
        cfg = self.cfg
        x, aux = self.forward(params, batch)
        labels = batch["labels"]
        if cfg.loss_chunk:
            ce = L.chunked_loss(params, x, labels, cfg, cfg.loss_chunk)
        else:
            ce = L.cross_entropy(L.unembed(params, x, cfg), labels)
        return ce + aux

    def logits(self, params: Params, batch: Batch) -> jax.Array:
        x, _ = self.forward(params, batch)
        return L.unembed(params, x, self.cfg)

    # -- serving ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int) -> Any:
        cfg = self.cfg
        dt = L.dtype_of(cfg)
        if cfg.rwkv:
            per = W.init_rwkv_state(cfg, batch, dt)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.num_layers,) + a.shape).copy(), per)
        s = min(cache_len, cfg.window) if cfg.attention_kind == "local" \
            else cache_len
        kv = jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, s,
                        cfg.head_dim), dt)
        return {"k": kv, "v": kv}

    def prefill(self, params: Params, batch: Batch, cache_len: int):  # repro: jit-pure
        cfg = self.cfg
        x, positions = self._inputs(params, batch)
        kinds = cfg.period
        n = len(kinds)

        def body(x, layer_p):
            kvs = []
            for i, kind in enumerate(kinds):
                lp = _gather_layer(_member(layer_p, n, i), cfg)
                x, kv = decoder_layer_prefill(lp, x, cfg, positions,
                                              cache_len, kind)
                kvs.append(kv)
            return x, _stack_period(kvs, n)

        x, cache = jax.lax.scan(body, x, _by_period(params["layers"], n))
        cache = _unperiod(cache, n)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x[:, -1:], cfg)[:, 0]
        return logits, cache

    # -- paged serving (block-pooled KV cache; serving/kv_cache.py) ----------
    @property
    def supports_paged(self) -> bool:
        """Paged KV is wired for the standard GQA decoder stack: token
        inputs, global attention, standard or YaRN RoPE, and periods of
        full and window layers.  Recurrent / all-local / M-RoPE variants
        keep the dense path."""
        cfg = self.cfg
        kinds = set(cfg.period)
        return (cfg.input_kind == "tokens" and not cfg.rwkv
                and cfg.attention_kind == "global" and not cfg.mrope
                and kinds <= {"full", "window"}
                and ("window" not in kinds or bool(cfg.window))
                and cfg.num_layers % len(cfg.period) == 0)

    def paged_layers(self) -> Tuple[int, int]:
        """(full layers, window layers): the layers of the paged pool and
        of the window layers' KV rings."""
        kinds = self.cfg.period
        per = self.cfg.num_layers // len(kinds)
        win = kinds.count("window")
        return per * (len(kinds) - win), per * win

    def init_paged_pool(self, num_blocks: int, block_size: int,
                        ring_blocks: int = 0, ring_block_size: int = 0):
        """{"k","v"} [L_full, N, Hkv, bs, hd] for the full layers and, with
        window layers, {"ring_k","ring_v"} [L_window, ring_blocks, Hkv,
        ring_block_size, hd]: each batch row's ring of R blocks and the
        null block (``ring_blocks`` = 1 + rows * R)."""
        cfg = self.cfg
        full, win = self.paged_layers()
        dt = L.dtype_of(cfg)
        tail = (cfg.num_kv_heads, block_size, cfg.head_dim)
        # distinct buffers: the pool is donated through the jitted step and
        # a shared k/v array would be donated twice
        pool = {"k": jnp.zeros((full, num_blocks) + tail, dt),
                "v": jnp.zeros((full, num_blocks) + tail, dt)}
        if win:
            ring = (win, ring_blocks, cfg.num_kv_heads, ring_block_size,
                    cfg.head_dim)
            pool["ring_k"] = jnp.zeros(ring, dt)
            pool["ring_v"] = jnp.zeros(ring, dt)
        return pool

    def _paged_backbone(self, params: Params, tokens: jax.Array, pool,  # repro: jit-pure
                        block_tables: jax.Array, positions: jax.Array,
                        last_idx: jax.Array):
        """Shared body of the paged steps: embed, scan the layers against
        the block pool, final norm.  Returns (x [B, C, D], new pool).

        The whole [L, N, Hkv, bs, hd] pool rides in the scan's carry and
        each layer updates it in place at its own index: as scanned
        inputs and outputs, every layer's slice would be copied out and
        back on every step, whatever the number of live tokens.

        The scan runs over periods of the layer pattern (``cfg.period``;
        a dense model is a period of one layer): each layer's kind picks
        its pool, its RoPE and its window at trace time.  Window layers
        read and write each row's KV ring at addresses computed here from
        the row's index and positions (:func:`layers.ring_addresses`)."""
        cfg = self.cfg
        x = L.embed(params, tokens, cfg)
        kinds = cfg.period
        n = len(kinds)
        n_win = kinds.count("window")
        ring = valid = experts = None
        layers = params["layers"]
        if n_win:
            with jax.named_scope("ring_placement"):
                ring = L.ring_addresses(block_tables, positions,
                                        pool["ring_k"].shape[1], cfg.window,
                                        pool["k"].shape[3],
                                        pool["ring_k"].shape[3])
        if cfg.moe is not None:
            # padding tokens and idle rows route to no expert
            valid = (jax.lax.broadcasted_iota(jnp.int32, tokens.shape, 1)
                     <= last_idx[:, None]) & (block_tables[:, :1] != 0)
            # the expert weights stay out of the scanned layers: a grouped
            # matmul would copy out each layer's slice of them, so it reads
            # the whole stack, at the layer's groups (moe_ffn_dropless)
            moe = layers["moe"]
            experts = {k: moe[k].reshape((-1,) + moe[k].shape[2:])
                       for k in ("w_gate", "w_up", "w_down")}
            layers = dict(layers, moe={k: v for k, v in moe.items()
                                       if k not in experts})

        def body(carry, xs):
            x, pools = carry
            layer_p, period = xs
            pools = dict(pools)
            seen = {"full": 0, "window": 0}
            for i, kind in enumerate(kinds):
                depth = period * n + i
                # a period's layers are indexed in the whole stack: a
                # period's slice of it would be copied out
                lp = _gather_layer(layer_p if n == 1 else jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, depth, keepdims=False), layers), cfg)
                k, v = ("ring_k", "ring_v") if kind == "window" else ("k", "v")
                count = n_win if kind == "window" else n - n_win
                idx = period * count + seen[kind]
                seen[kind] += 1
                x, (pools[k], pools[v]) = decoder_layer_paged(
                    lp, x, cfg, pools[k], pools[v], idx, block_tables,
                    positions, last_idx=last_idx, kind=kind,
                    ring=ring if kind == "window" else None, valid=valid,
                    experts=experts, depth=depth)
            return (x, pools), None

        periods = jnp.arange(cfg.num_layers // n, dtype=jnp.int32)
        (x, pool), _ = jax.lax.scan(
            body, (x, dict(pool)), (layers if n == 1 else None, periods))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, pool

    def paged_step(self, params: Params, tokens: jax.Array, pool,  # repro: jit-pure
                   block_tables: jax.Array, positions: jax.Array,
                   last_idx: jax.Array):
        """Advance up to C tokens per row against the paged pool.

        tokens: [B, C] (decode: C == 1; chunked prefill: C == chunk;
        mixed prefill/decode step: one fixed width C for every row);
        pool: {"k","v"} [L, N, Hkv, bs, hd]; block_tables: [B, M] int32;
        positions: [B, C] absolute positions; last_idx: [B] per-row index
        of each row's last *valid* token within the chunk — a decode row
        advances 1 token (last_idx 0), a prefilling row advances
        ``last_idx + 1`` prompt tokens, and padding past last_idx writes
        only to the null block.  Returns (logits [B, V] at last_idx,
        new pool) — raw logits, never an argmax: token selection is the
        scheduler's job (greedy argmax or the seeded sampler in
        serving/sampling.py), so one compiled step serves both.
        """
        x, pool = self._paged_backbone(params, tokens, pool, block_tables,
                                       positions, last_idx)
        x_last = jnp.take_along_axis(
            x, last_idx[:, None, None].astype(jnp.int32), axis=1)  # [B,1,D]
        logits = L.unembed(params, x_last, self.cfg)[:, 0]
        return logits, pool

    def paged_step_verify(self, params: Params, tokens: jax.Array, pool,  # repro: jit-pure
                          block_tables: jax.Array, positions: jax.Array,
                          last_idx: jax.Array):
        """Speculative-decoding verifier: :meth:`paged_step`, but with
        logits at EVERY chunk position, not just the last valid one.

        Row layout: ``tokens[b, 0]`` is the row's committed pending token
        and ``tokens[b, 1:last_idx[b]+1]`` its drafted continuation.  The
        returned ``logits[b, j]`` scores the vocabulary after the row has
        consumed tokens ``0..j`` — so ``argmax(logits[b, j]) ==
        tokens[b, j+1]`` is exactly "draft j+1 verified", and the first
        mismatch's argmax is the fallback token the sequential decode
        would have produced.  At temperature > 0 the engine instead
        feeds these per-position logits to rejection sampling
        (serving/sampling.py), which is why the verifier returns full
        logits rather than deciding acceptance itself.  Positions past
        ``last_idx`` are padding: their K/V writes go to the null block
        and their logits are garbage the engine never reads.  Returns
        (logits [B, C, V], new pool).
        """
        x, pool = self._paged_backbone(params, tokens, pool, block_tables,
                                       positions, last_idx)
        return L.unembed(params, x, self.cfg), pool

    def decode_step(self, params: Params, tokens: jax.Array, cache, pos):  # repro: jit-pure
        """tokens: [B, 1]; pos: scalar absolute position."""
        cfg = self.cfg
        x = L.embed(params, tokens, cfg) if cfg.input_kind != "embeddings" \
            else tokens  # embeddings-input archs decode from token ids too
        if cfg.input_kind == "embeddings":
            x = L.embed(params, tokens, cfg)

        kinds = cfg.period
        n = len(kinds)

        def body(x, xs):
            layer_p, layer_cache = xs
            new = []
            for i, kind in enumerate(kinds):
                lp = _gather_layer(_member(layer_p, n, i), cfg)
                x, c = decoder_layer_decode(lp, x, cfg,
                                            _member(layer_cache, n, i), pos,
                                            kind)
                new.append(c)
            return x, _stack_period(new, n)

        x, new_cache = jax.lax.scan(body, x, (_by_period(params["layers"], n),
                                              _by_period(cache, n)))
        new_cache = _unperiod(new_cache, n)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x, cfg)[:, 0]
        return logits, new_cache


# ==========================================================================
# HybridLM (RecurrentGemma): scanned super-blocks + tail
# ==========================================================================


def init_hybrid_super(key, cfg: ModelConfig) -> Params:
    """One super-block = cfg.block_pattern of temporal blocks, each + MLP."""
    out: Params = {}
    ks = jax.random.split(key, len(cfg.block_pattern))
    dt = L.dtype_of(cfg)
    for i, kind in enumerate(cfg.block_pattern):
        sub = {"ln1": L.init_norm(cfg.d_model, dt),
               "ln2": L.init_norm(cfg.d_model, dt),
               "mlp": L.init_mlp(jax.random.fold_in(ks[i], 1), cfg)}
        if kind == "rec":
            sub["rec"] = R.init_recurrent_block(ks[i], cfg)
        else:
            sub["attn"] = L.init_attention(ks[i], cfg)
        out[f"b{i}"] = sub
    return out


class HybridLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        assert cfg.block_pattern
        self.n_super = (cfg.num_layers - len(cfg.tail_pattern)) \
            // len(cfg.block_pattern)

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_sup, k_tail = jax.random.split(key, 3)
        sup_keys = jax.random.split(k_sup, self.n_super)
        stacked = jax.vmap(lambda k: init_hybrid_super(k, cfg))(sup_keys)
        p = L.init_embedding(k_emb, cfg)
        p["supers"] = stacked
        tail = {}
        tks = jax.random.split(k_tail, max(len(cfg.tail_pattern), 1))
        dt = L.dtype_of(cfg)
        for i, kind in enumerate(cfg.tail_pattern):
            sub = {"ln1": L.init_norm(cfg.d_model, dt),
                   "ln2": L.init_norm(cfg.d_model, dt),
                   "mlp": L.init_mlp(jax.random.fold_in(tks[i], 1), cfg)}
            if kind == "rec":
                sub["rec"] = R.init_recurrent_block(tks[i], cfg)
            else:
                sub["attn"] = L.init_attention(tks[i], cfg)
            tail[f"t{i}"] = sub
        p["tail"] = tail
        p["final_norm"] = L.init_norm(cfg.d_model, dt)
        return p

    def _block_train(self, sub: Params, kind: str, x, positions):
        cfg = self.cfg
        h = L.rms_norm(x, sub["ln1"], cfg.norm_eps)
        if kind == "rec":
            y, _ = R.recurrent_block(sub["rec"], h, cfg)
        else:
            y = L.attention_train(sub["attn"], h, cfg, positions,
                                  window=cfg.window)
        x = x + y
        h = L.rms_norm(x, sub["ln2"], cfg.norm_eps)
        return x + L.mlp(sub["mlp"], h, cfg)

    def forward(self, params: Params, batch: Batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed(params, tokens, cfg)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def body(x, sup):
            for i, kind in enumerate(cfg.block_pattern):
                x = self._block_train(sup[f"b{i}"], kind, x, positions)
            return x, None

        body_fn = body
        if cfg.remat == "full":
            body_fn = jax.checkpoint(body,
                                     policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body_fn, x, params["supers"])
        for i, kind in enumerate(cfg.tail_pattern):
            x = self._block_train(params["tail"][f"t{i}"], kind, x, positions)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.float32(0)

    def loss(self, params: Params, batch: Batch) -> jax.Array:
        cfg = self.cfg
        x, aux = self.forward(params, batch)
        if cfg.loss_chunk:
            return L.chunked_loss(params, x, batch["labels"], cfg,
                                  cfg.loss_chunk) + aux
        return L.cross_entropy(L.unembed(params, x, cfg),
                               batch["labels"]) + aux

    def logits(self, params: Params, batch: Batch) -> jax.Array:
        x, _ = self.forward(params, batch)
        return L.unembed(params, x, self.cfg)

    # -- serving ---------------------------------------------------------------
    def _empty_block_cache(self, kind: str, batch: int, cache_len: int):
        cfg = self.cfg
        dt = L.dtype_of(cfg)
        if kind == "rec":
            return R.init_recurrent_state(cfg, batch, dt)
        s = min(cache_len, cfg.window or cache_len)
        kv = jnp.zeros((batch, cfg.num_kv_heads, s, cfg.head_dim), dt)
        return {"k": kv, "v": kv}

    def init_cache(self, batch: int, cache_len: int):
        cfg = self.cfg
        sup = {}
        for i, kind in enumerate(cfg.block_pattern):
            per = self._empty_block_cache(kind, batch, cache_len)
            sup[f"b{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (self.n_super,) + a.shape).copy(), per)
        tail = {f"t{i}": self._empty_block_cache(kind, batch, cache_len)
                for i, kind in enumerate(cfg.tail_pattern)}
        return {"supers": sup, "tail": tail}

    def _block_decode(self, sub: Params, kind: str, x, cache, pos):
        cfg = self.cfg
        h = L.rms_norm(x, sub["ln1"], cfg.norm_eps)
        if kind == "rec":
            y, new_cache = R.recurrent_block(sub["rec"], h, cfg, state=cache)
        else:
            y, new_cache = L.attention_decode(sub["attn"], h, cfg, cache, pos,
                                              window=cfg.window)
        x = x + y
        h = L.rms_norm(x, sub["ln2"], cfg.norm_eps)
        return x + L.mlp(sub["mlp"], h, cfg), new_cache

    def _block_prefill(self, sub: Params, kind: str, x, positions,
                       cache_len: int):
        cfg = self.cfg
        h = L.rms_norm(x, sub["ln1"], cfg.norm_eps)
        if kind == "rec":
            y, state = R.recurrent_block(sub["rec"], h, cfg)
            new_cache = state
        else:
            y, new_cache = L.attention_prefill(
                sub["attn"], h, cfg, positions,
                min(cache_len, cfg.window or cache_len), window=cfg.window)
        x = x + y
        h = L.rms_norm(x, sub["ln2"], cfg.norm_eps)
        return x + L.mlp(sub["mlp"], h, cfg), new_cache

    def prefill(self, params: Params, batch: Batch, cache_len: int):  # repro: jit-pure
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed(params, tokens, cfg)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def body(x, sup):
            caches = {}
            for i, kind in enumerate(cfg.block_pattern):
                x, c = self._block_prefill(sup[f"b{i}"], kind, x, positions,
                                           cache_len)
                caches[f"b{i}"] = c
            return x, caches

        x, sup_cache = jax.lax.scan(body, x, params["supers"])
        tail_cache = {}
        for i, kind in enumerate(cfg.tail_pattern):
            x, c = self._block_prefill(params["tail"][f"t{i}"], kind, x,
                                       positions, cache_len)
            tail_cache[f"t{i}"] = c
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x[:, -1:], cfg)[:, 0]
        return logits, {"supers": sup_cache, "tail": tail_cache}

    def decode_step(self, params: Params, tokens, cache, pos):  # repro: jit-pure
        cfg = self.cfg
        x = L.embed(params, tokens, cfg)

        def body(x, xs):
            sup, sup_cache = xs
            new = {}
            for i, kind in enumerate(cfg.block_pattern):
                x, c = self._block_decode(sup[f"b{i}"], kind, x,
                                          sup_cache[f"b{i}"], pos)
                new[f"b{i}"] = c
            return x, new

        x, new_sup = jax.lax.scan(body, x,
                                  (params["supers"], cache["supers"]))
        new_tail = {}
        for i, kind in enumerate(cfg.tail_pattern):
            x, c = self._block_decode(params["tail"][f"t{i}"], kind, x,
                                      cache["tail"][f"t{i}"], pos)
            new_tail[f"t{i}"] = c
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x, cfg)[:, 0]
        return logits, {"supers": new_sup, "tail": new_tail}


# ==========================================================================
# EncDecLM (Seamless backbone): frame-embedding encoder + token decoder
# ==========================================================================


def init_encoder_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    dt = L.dtype_of(cfg)
    return {
        "ln1": L.init_norm(cfg.d_model, dt),
        "ln2": L.init_norm(cfg.d_model, dt),
        "attn": L.init_attention(ks[0], cfg),
        "mlp": L.init_mlp(ks[1], cfg),
    }


def init_decdec_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = L.dtype_of(cfg)
    return {
        "ln1": L.init_norm(cfg.d_model, dt),
        "ln_x": L.init_norm(cfg.d_model, dt),
        "ln2": L.init_norm(cfg.d_model, dt),
        "attn": L.init_attention(ks[0], cfg),
        "xattn": L.init_cross_attention(ks[1], cfg),
        "mlp": L.init_mlp(ks[2], cfg),
    }


class EncDecLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_enc, k_dec = jax.random.split(key, 3)
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
        dec_keys = jax.random.split(k_dec, cfg.num_layers)
        p = L.init_embedding(k_emb, cfg)
        p["encoder"] = jax.vmap(lambda k: init_encoder_layer(k, cfg))(enc_keys)
        p["decoder"] = jax.vmap(lambda k: init_decdec_layer(k, cfg))(dec_keys)
        dt = L.dtype_of(cfg)
        p["enc_norm"] = L.init_norm(cfg.d_model, dt)
        p["final_norm"] = L.init_norm(cfg.d_model, dt)
        return p

    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = frames.astype(L.dtype_of(cfg))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        def body(x, layer_p):
            h = L.rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            x = x + L.attention_train(layer_p["attn"], h, cfg, positions,
                                      causal=False)
            h = L.rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            return x + L.mlp(layer_p["mlp"], h, cfg), None

        body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
        x, _ = jax.lax.scan(body_fn, x, params["encoder"])
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _decode_train(self, params: Params, tokens, memory):
        cfg = self.cfg
        x = L.embed(params, tokens, cfg)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def body(x, layer_p):
            h = L.rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            x = x + L.attention_train(layer_p["attn"], h, cfg, positions)
            h = L.rms_norm(x, layer_p["ln_x"], cfg.norm_eps)
            x = x + L.cross_attention(layer_p["xattn"], h, memory, cfg)
            h = L.rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            return x + L.mlp(layer_p["mlp"], h, cfg), None

        body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
        x, _ = jax.lax.scan(body_fn, x, params["decoder"])
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)

    def forward(self, params: Params, batch: Batch):
        memory = self.encode(params, batch["frames"])
        x = self._decode_train(params, batch["tokens"], memory)
        return x, jnp.float32(0)

    def loss(self, params: Params, batch: Batch) -> jax.Array:
        cfg = self.cfg
        x, _ = self.forward(params, batch)
        if cfg.loss_chunk:
            return L.chunked_loss(params, x, batch["labels"], cfg,
                                  cfg.loss_chunk)
        return L.cross_entropy(L.unembed(params, x, cfg), batch["labels"])

    def logits(self, params: Params, batch: Batch) -> jax.Array:
        x, _ = self.forward(params, batch)
        return L.unembed(params, x, self.cfg)

    # -- serving -----------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int):
        cfg = self.cfg
        dt = L.dtype_of(cfg)
        kv = jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, cache_len,
                        cfg.head_dim), dt)
        mem_len = max(cache_len // cfg.frame_ratio, 1)
        return {"k": kv, "v": kv,
                "memory": jnp.zeros((batch, mem_len, cfg.d_model), dt)}

    def prefill(self, params: Params, batch: Batch, cache_len: int):  # repro: jit-pure
        cfg = self.cfg
        memory = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        x = L.embed(params, tokens, cfg)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def body(x, layer_p):
            h = L.rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            att, kv = L.attention_prefill(layer_p["attn"], h, cfg, positions,
                                          cache_len)
            x = x + att
            h = L.rms_norm(x, layer_p["ln_x"], cfg.norm_eps)
            x = x + L.cross_attention(layer_p["xattn"], h, memory, cfg)
            h = L.rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            return x + L.mlp(layer_p["mlp"], h, cfg), kv

        x, kv = jax.lax.scan(body, x, params["decoder"])
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x[:, -1:], cfg)[:, 0]
        return logits, {"k": kv["k"], "v": kv["v"], "memory": memory}

    def decode_step(self, params: Params, tokens, cache, pos):  # repro: jit-pure
        cfg = self.cfg
        x = L.embed(params, tokens, cfg)
        memory = cache["memory"]

        def body(x, xs):
            layer_p, layer_cache = xs
            h = L.rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            att, kv = L.attention_decode(layer_p["attn"], h, cfg, layer_cache,
                                         pos)
            x = x + att
            h = L.rms_norm(x, layer_p["ln_x"], cfg.norm_eps)
            x = x + L.cross_attention(layer_p["xattn"], h, memory, cfg)
            h = L.rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            return x + L.mlp(layer_p["mlp"], h, cfg), kv

        x, new_kv = jax.lax.scan(
            body, x, (params["decoder"], {"k": cache["k"], "v": cache["v"]}))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, x, cfg)[:, 0]
        return logits, {"k": new_kv["k"], "v": new_kv["v"], "memory": memory}


# ==========================================================================
# Registry
# ==========================================================================


def get_model(cfg: ModelConfig):
    if cfg.encoder_layers:
        return EncDecLM(cfg)
    if cfg.block_pattern:
        return HybridLM(cfg)
    return DecoderLM(cfg)
