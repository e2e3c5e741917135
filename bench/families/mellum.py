"""The Mellum2 family: periods of sliding-window and full GQA layers, each
followed by a sparse expert layer, as JetBrains/Mellum2-12B-A2.5B's
config.json describes them.

A layer: RMSNorm, grouped-query attention without bias, RMSNorm, then a
mixture of experts: the router's softmax over every expert
(``experts_published``), its top ``num_experts_per_tok`` weights
renormalised (``norm_topk_prob``), and each chosen expert a SwiGLU MLP of
width ``moe_intermediate_size``.  ``layer_types`` gives each layer's kind:
a ``sliding_attention`` layer reads the last ``sliding_window`` keys
(HF's mask ``kv_idx > q_idx - sliding_window``) with plain RoPE; a
``full_attention`` layer reads every key, with YaRN RoPE as
``rope_parameters`` states it (HF's ``_compute_yarn_parameters``,
truncated correction range; cos and sin times ``attention_factor``).
A final RMSNorm and an untied head follow.

The configuration holds a share of the experts: ``num_experts`` of them,
from ``experts_held_first``, of the ``experts_published`` the router
chooses from.  The reference adds only the held experts' part of each
layer's output, as the program does: the part the absent experts would add
lies on other chips.

See ``dense.py`` for what a family module gives the harness; this one
also gives ``expert_work``, the held experts' operations and bytes for a
step, read by ``metrics/moe_roofline.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import dtype_bytes
from bench.reference import F32, Q_BLOCK, _bucket, _mm, _rms
from bench.weights import _normal

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a Mellum2 model, from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    period: Tuple[str, ...]      # "window" / "full" kinds of one period
    window: int
    theta: float                 # both kinds' RoPE base
    yarn: Tuple[float, int, float, float, float]  # factor, original
    # positions, beta_fast, beta_slow, attention_factor
    experts: int                 # the router's experts
    top_k: int
    held_first: int
    held: int
    d_expert: int
    embed_mult: float
    dtype: str

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def kind(self, layer: int) -> str:
        return self.period[layer % len(self.period)]

    def count(self, kind: str) -> int:
        """Layers of ``kind`` in the model."""
        return self.layers // len(self.period) * self.period.count(kind)


def _period(kinds: Sequence[str]) -> Tuple[str, ...]:
    """The shortest period the layer kinds repeat with."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and all(
                kinds[i] == kinds[i % p] for i in range(len(kinds))):
            return tuple(kinds[:p])
    raise AssertionError("unreachable")


def dims(config: dict) -> Dims:
    """``Dims`` of a configuration file: the published config.json keys at
    its top level, ``program`` for what they leave implicit."""
    c, p = config, config["program"]
    rp = c["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
    assert full["rope_theta"] == sliding["rope_theta"]
    assert c["norm_topk_prob"] and not c["attention_bias"]
    assert not c["tie_word_embeddings"]
    assert set(c["mlp_layer_types"]) == {"sparse"}
    return Dims(
        layers=c["num_hidden_layers"], d=c["hidden_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], vocab=c["vocab_size"], eps=c["rms_norm_eps"],
        period=_period([_KINDS[k] for k in c["layer_types"]]),
        window=c["sliding_window"], theta=float(full["rope_theta"]),
        yarn=(float(full["factor"]),
              int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]),
              float(full["attention_factor"])),
        experts=c["experts_published"], top_k=c["num_experts_per_tok"],
        held_first=c["experts_held_first"], held=c["num_experts"],
        d_expert=c["moe_intermediate_size"],
        embed_mult=float(p["embedding_multiplier"]), dtype=p["dtype"])


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig, YarnRope
    m = dims(config)
    factor, orig, fast, slow, att = m.yarn
    return ModelConfig(
        name=config["name"], family="moe", num_layers=m.layers, d_model=m.d,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.d_expert, vocab_size=m.vocab, rope_theta=m.theta,
        norm_eps=m.eps, window=m.window, layer_pattern=m.period,
        rope_scaling=YarnRope(factor=factor, original_max_positions=orig,
                              beta_fast=fast, beta_slow=slow,
                              attention_factor=att),
        moe=MoEConfig(num_experts=m.experts, top_k=m.top_k,
                      d_expert=m.d_expert, held_first=m.held_first,
                      held=m.held),
        dtype=m.dtype)


# -- weights ---------------------------------------------------------------------


def _layer_weights(key, m: Dims):
    dt = jnp.dtype(m.dtype)
    k = jax.random.split(key, 9)
    h, d, fe = m.held, m.d, m.d_expert
    attn = {"wq": _normal(k[0], (d, m.q_dim), d ** -0.5, dt),
            "wk": _normal(k[1], (d, m.kv_dim), d ** -0.5, dt),
            "wv": _normal(k[2], (d, m.kv_dim), d ** -0.5, dt),
            "wo": _normal(k[3], (m.q_dim, d), m.q_dim ** -0.5, dt)}
    moe = {"router": _normal(k[4], (d, m.experts), d ** -0.5, jnp.float32),
           "w_gate": _normal(k[5], (h, d, fe), d ** -0.5, dt),
           "w_up": _normal(k[6], (h, d, fe), d ** -0.5, dt),
           "w_down": _normal(k[7], (h, fe, d), fe ** -0.5, dt)}
    kn = jax.random.split(k[8])
    return {"ln1": (1 + _normal(kn[0], (d,), 0.1, jnp.float32)).astype(dt),
            "ln2": (1 + _normal(kn[1], (d,), 0.1, jnp.float32)).astype(dt),
            "attn": attn, "moe": moe}


@functools.partial(jax.jit, static_argnums=(1,))
def make(key, m: Dims):
    """Every weight of the model from ``key``: one call, on the device."""
    dt = jnp.dtype(m.dtype)
    k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
    return {"embed": _normal(k_emb, (m.vocab, m.d), 0.02 / m.embed_mult, dt),
            "layers": jax.vmap(lambda k: _layer_weights(k, m))(
                jax.random.split(k_layers, m.layers)),
            "final_norm": (1 + _normal(k_norm, (m.d,), 0.1, jnp.float32)
                           ).astype(dt),
            "lm_head": _normal(k_head, (m.d, m.vocab), m.d ** -0.5, dt)}


# -- the reference ---------------------------------------------------------------


def _inv_freq(m: Dims, kind: str):
    """Inverse frequencies and the cos/sin factor of a layer's RoPE."""
    hd = m.head_dim
    base = 1.0 / m.theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if kind == "window":
        return base, 1.0
    factor, orig, fast, slow, att = m.yarn

    def corr(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            m.theta))
    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                    hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return (base / factor * (1 - extrapolate) + base * extrapolate), att


def _rope(x, m: Dims, kind: str):
    """x [H, S, hd] at positions 0..S-1."""
    hd, s = x.shape[-1], x.shape[-2]
    inv, mult = _inv_freq(m, kind)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    c, sn = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _attention(q, k, v, m: Dims, window: Optional[int]):
    """Causal GQA, windowed if ``window``, in query blocks.
    q [H, S, hd]; k, v [Hkv, S, hd] -> [S, H * hd]."""
    h, s, hd = q.shape
    g = h // m.kv_heads
    qb = min(Q_BLOCK, s)
    blocks = q.reshape(m.kv_heads, g, s // qb, qb, hd).transpose(2, 0, 1, 3, 4)

    def one(args):
        qi, i = args                                # [Hkv, g, qb, hd]
        sc = jnp.einsum("kgqd,ksd->kgqs", qi, k) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)[:, None]
        kpos = jnp.arange(s)[None, :]
        keep = kpos <= qpos
        if window:
            keep &= kpos > qpos - window
        sc = jnp.where(keep, sc, -jnp.inf)
        return jnp.einsum("kgqs,ksd->kgqd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one, (blocks, jnp.arange(s // qb)))  # [nb,Hkv,g,qb,hd]
    return o.transpose(0, 3, 1, 2, 4).reshape(s, h * hd)


def _experts(h, e, m: Dims, quant: Optional[str]):
    """The held experts' part of the layer's output for tokens h [S, D]:
    the router's top-k of the softmax over every expert, renormalised;
    each held expert computed for every token and weighted by its routing
    weight (0 where it was not chosen)."""
    probs = jax.nn.softmax(_mm(h, e["router"], quant), -1)      # [S, E]
    top, idx = jax.lax.top_k(probs, m.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, m.experts, dtype=F32)          # [S, k, E]
    weight = jnp.einsum("sk,ske->se", top, chosen)              # [S, E]

    def one(acc, j):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(e[n], j, keepdims=False)
                      for n in ("w_gate", "w_up", "w_down"))
        y = _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd,
                quant)
        w = jax.lax.dynamic_index_in_dim(weight, m.held_first + j, 1)
        return acc + w * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(m.held))
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, layers, i, m: Dims, kind: str, quant: Optional[str]):
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(F32), layers)
    a = p["attn"]
    s = x.shape[0]
    h = _rms(x, p["ln1"], m.eps)
    q, k, v = (_mm(h, a[w], quant) for w in ("wq", "wk", "wv"))
    q = _rope(q.reshape(s, m.heads, m.head_dim).transpose(1, 0, 2), m, kind)
    k = _rope(k.reshape(s, m.kv_heads, m.head_dim).transpose(1, 0, 2), m,
              kind)
    v = v.reshape(s, m.kv_heads, m.head_dim).transpose(1, 0, 2)
    att = _attention(q, k, v, m, m.window if kind == "window" else None)
    x = x + _mm(att, a["wo"], quant)
    return x + _experts(_rms(x, p["ln2"], m.eps), p["moe"], m, quant)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(params, tokens, m: Dims):
    return params["embed"][tokens].astype(F32) * m.embed_mult


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(params, x, rows, m: Dims, quant: Optional[str]):
    h = _rms(x[rows], params["final_norm"].astype(F32), m.eps)
    return _mm(h, params["lm_head"].astype(F32), quant)


def logits(params, m: Dims, tokens: np.ndarray, rows: Sequence[int], *,
           quant: Optional[str] = None) -> jax.Array:
    """[len(rows), vocab] float32 logits after ``tokens[: row + 1]``.

    One sequence, layer by layer, each layer's attention in query blocks
    of ``Q_BLOCK``.  The sequence is padded to a power-of-two length
    (causal attention leaves earlier positions untouched), so few shapes
    compile."""
    s = _bucket(len(tokens))
    padded = np.zeros(s, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params, jnp.asarray(padded), m)
        for i in range(m.layers):
            x = _layer(x, params["layers"], jnp.int32(i), m, m.kind(i),
                       quant)
        return _head(params, x, jnp.asarray(np.asarray(rows, np.int32)), m,
                     quant)


# -- operations and bytes --------------------------------------------------------


def expert_params(m: Dims) -> int:
    """Weights of one expert."""
    return 3 * m.d * m.d_expert


def routed_experts(m: Dims) -> float:
    """Held experts a token reaches, on average: top_k x held / experts
    (8 x 16 / 64 = 2 in the cell).  Uniform routing is assumed: the
    router's choice depends on the weights and the tokens, and a count
    from the step's inputs cannot see it."""
    return m.top_k * m.held / m.experts


def layer_matmul_params(m: Dims) -> float:
    """Weights one token multiplies by in one layer: Q, K, V, O, the
    router and the held experts it reaches."""
    return (2 * m.d * m.q_dim + 2 * m.d * m.kv_dim + m.d * m.experts
            + routed_experts(m) * expert_params(m))


def token_flops(m: Dims, ctx: np.ndarray) -> np.ndarray:
    """FLOPs of the layers for tokens that attend to ``ctx`` keys each:
    2 per weight, and 4 * heads * head_dim per key read (a window layer
    reads at most ``window`` keys)."""
    ctx = np.asarray(ctx, np.float64)
    keys = (m.count("full") * ctx
            + m.count("window") * np.minimum(ctx, m.window))
    return (m.layers * 2.0 * layer_matmul_params(m)
            + 4.0 * m.heads * m.head_dim * keys)


def head_flops(m: Dims) -> float:
    """FLOPs of the output head for one token's logits."""
    return 2.0 * m.d * m.vocab


def attention_work(m: Dims, rows: Iterable[np.ndarray]
                   ) -> List[Tuple[int, float, float]]:
    """``[(window layers, flops, bytes), (full layers, flops, bytes)]``:
    the paged attention work of one step in each kind of layer.

    ``rows``: for each live row, the positions of its valid query tokens.
    In a full layer a token at position p reads p + 1 keys, and a row
    reads its keys and values once, up to its last position; in a window
    layer the token reads min(p + 1, window) keys, and the row reads only
    the keys of its tokens' windows.  Queries are read and outputs
    written once."""
    b = dtype_bytes(m)
    work = {"window": [0.0, 0.0], "full": [0.0, 0.0]}
    for pos in rows:
        pos = np.asarray(pos, np.float64)
        if not pos.size:
            continue
        io = 2.0 * pos.size * m.heads * m.head_dim * b
        lo = max(0.0, float(pos.min()) - m.window + 1)
        for kind, keys, span in (
                ("full", pos + 1, float(pos.max()) + 1),
                ("window", np.minimum(pos + 1, m.window),
                 float(pos.max()) + 1 - lo)):
            work[kind][0] += 4.0 * m.heads * m.head_dim * float(np.sum(keys))
            work[kind][1] += 2.0 * m.kv_heads * m.head_dim * span * b + io
    return [(m.count(k), *work[k]) for k in ("window", "full")]


def expert_work(m: Dims, rows: Iterable[np.ndarray]
                ) -> Tuple[int, float, float]:
    """``(layers, flops, bytes)``: the held experts' work in each layer for
    one step's valid tokens (``rows`` as in :func:`attention_work`).

    FLOPs: 2 per expert weight for each (token, held expert) pair, with
    ``routed_experts`` pairs a token.  Bytes: every held expert's weights
    once a step (a step of 16 rows reaches all 16 experts), and each
    pair's input and output row."""
    tokens = float(sum(np.asarray(p).size for p in rows))
    pairs = tokens * routed_experts(m)
    b = dtype_bytes(m)
    return (m.layers, 2.0 * expert_params(m) * pairs,
            (m.held * expert_params(m) + 2.0 * pairs * m.d) * b)
