"""The dense decoder family: Qwen2 and Llama (Yi) blocks as published.

RMSNorm, grouped-query attention with rotary positions (rotate-half,
``rope_theta``), optional QKV bias, a SwiGLU MLP, a final RMSNorm and the
output head (the input embedding's transpose where the embeddings are
tied).  One departure, stated in the configuration file: the input
embedding is multiplied by ``program.embedding_multiplier`` (the served
program scales tied embeddings by sqrt(hidden_size) rounded to bfloat16,
as Gemma does; 1 elsewhere).

A family module gives the harness everything that depends on the block:

- ``dims(config)``: the frozen sizes of a configuration file;
- ``program_config(config)``: the program's ``ModelConfig``;
- ``make(key, dims)``: the seeded weights, in the program's layout, made
  on the device in one jitted call;
- ``logits(params, dims, tokens, rows, *, quant=None)``: the plain
  float32 reference, and with ``quant="fp8"`` the control;
- ``token_flops(dims, ctx)``, ``head_flops(dims)``: model FLOPs;
- ``attention_work(dims, rows)``: ``(layer_count, flops, bytes)`` for
  each kind of attention layer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import dtype_bytes
from bench.reference import F32, Q_BLOCK, _bucket, _mm, _rms
from bench.weights import _normal


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a decoder-only model, from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    eps: float
    theta: float
    embed_mult: float
    dtype: str

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def dims(config: dict) -> Dims:
    """``Dims`` of a configuration file (its ``model`` object holds the
    published config.json keys, its ``program`` object what the
    published config leaves implicit)."""
    m, p = config["model"], config["program"]
    return Dims(layers=m["num_hidden_layers"], d=m["hidden_size"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"],
                head_dim=m.get("head_dim",
                               m["hidden_size"] // m["num_attention_heads"]),
                ff=m["intermediate_size"], vocab=m["vocab_size"],
                qkv_bias=bool(p["qkv_bias"]),
                tied=bool(m["tie_word_embeddings"]), eps=m["rms_norm_eps"],
                theta=m["rope_theta"],
                embed_mult=float(p["embedding_multiplier"]),
                dtype=m["torch_dtype"])


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    m = dims(config)
    return ModelConfig(
        name=config["name"], family="dense", num_layers=m.layers,
        d_model=m.d, num_heads=m.heads, num_kv_heads=m.kv_heads,
        head_dim=m.head_dim, d_ff=m.ff, vocab_size=m.vocab,
        qkv_bias=m.qkv_bias, rope_theta=m.theta, norm_eps=m.eps,
        tie_embeddings=m.tied, dtype=m.dtype)


# -- weights ---------------------------------------------------------------------


def _layer_weights(key, m: Dims):
    dt = jnp.dtype(m.dtype)
    k = jax.random.split(key, 12)
    attn = {"wq": _normal(k[0], (m.d, m.q_dim), m.d ** -0.5, dt),
            "wk": _normal(k[1], (m.d, m.kv_dim), m.d ** -0.5, dt),
            "wv": _normal(k[2], (m.d, m.kv_dim), m.d ** -0.5, dt),
            "wo": _normal(k[3], (m.q_dim, m.d), m.q_dim ** -0.5, dt)}
    if m.qkv_bias:
        attn["bq"] = _normal(k[4], (m.q_dim,), 0.1, dt)
        attn["bk"] = _normal(k[5], (m.kv_dim,), 0.1, dt)
        attn["bv"] = _normal(k[6], (m.kv_dim,), 0.1, dt)
    mlp = {"w_gate": _normal(k[7], (m.d, m.ff), m.d ** -0.5, dt),
           "w_up": _normal(k[8], (m.d, m.ff), m.d ** -0.5, dt),
           "w_down": _normal(k[9], (m.ff, m.d), m.ff ** -0.5, dt)}
    return {"ln1": (1 + _normal(k[10], (m.d,), 0.1, jnp.float32)).astype(dt),
            "ln2": (1 + _normal(k[11], (m.d,), 0.1, jnp.float32)).astype(dt),
            "attn": attn, "mlp": mlp}


@functools.partial(jax.jit, static_argnums=(1,))
def make(key, m: Dims):
    """Every weight of the model from ``key``: one call, on the device."""
    dt = jnp.dtype(m.dtype)
    k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
    p = {"embed": _normal(k_emb, (m.vocab, m.d), 0.02 / m.embed_mult, dt),
         "layers": jax.vmap(lambda k: _layer_weights(k, m))(
             jax.random.split(k_layers, m.layers)),
         "final_norm": (1 + _normal(k_norm, (m.d,), 0.1, jnp.float32)
                        ).astype(dt)}
    if not m.tied:
        p["lm_head"] = _normal(k_head, (m.d, m.vocab), m.d ** -0.5, dt)
    return p


# -- the reference ---------------------------------------------------------------


def _rope(x, theta):
    """x [H, S, hd] at positions 0..S-1."""
    hd, s = x.shape[-1], x.shape[-2]
    freqs = theta ** (-jnp.arange(hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    c, sn = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _attention(q, k, v, m: Dims):
    """Causal GQA.  q [H, S, hd]; k, v [Hkv, S, hd] -> [S, H * hd]."""
    h, s, hd = q.shape
    g = h // m.kv_heads
    qb = min(Q_BLOCK, s)
    blocks = q.reshape(m.kv_heads, g, s // qb, qb, hd).transpose(2, 0, 1, 3, 4)

    def one(args):
        qi, i = args                                # [Hkv, g, qb, hd]
        sc = jnp.einsum("kgqd,ksd->kgqs", qi, k) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos, sc, -jnp.inf)
        return jnp.einsum("kgqs,ksd->kgqd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one, (blocks, jnp.arange(s // qb)))  # [nb,Hkv,g,qb,hd]
    return o.transpose(0, 3, 1, 2, 4).reshape(s, h * hd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, layers, i, m: Dims, quant: Optional[str]):
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(F32), layers)
    a, f = p["attn"], p["mlp"]
    s = x.shape[0]
    h = _rms(x, p["ln1"], m.eps)
    q, k, v = (_mm(h, a[w], quant) for w in ("wq", "wk", "wv"))
    if m.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(s, m.heads, m.head_dim).transpose(1, 0, 2), m.theta)
    k = _rope(k.reshape(s, m.kv_heads, m.head_dim).transpose(1, 0, 2),
              m.theta)
    v = v.reshape(s, m.kv_heads, m.head_dim).transpose(1, 0, 2)
    x = x + _mm(_attention(q, k, v, m), a["wo"], quant)
    h = _rms(x, p["ln2"], m.eps)
    act = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    return x + _mm(act, f["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(params, tokens, m: Dims):
    return params["embed"][tokens].astype(F32) * m.embed_mult


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(params, x, rows, m: Dims, quant: Optional[str]):
    h = _rms(x[rows], params["final_norm"].astype(F32), m.eps)
    w = params["embed"].T if m.tied else params["lm_head"]
    return _mm(h, w.astype(F32), quant)


def logits(params, m: Dims, tokens: np.ndarray, rows: Sequence[int], *,
           quant: Optional[str] = None) -> jax.Array:
    """[len(rows), vocab] float32 logits after ``tokens[: row + 1]``.

    One sequence, full causal attention, layer by layer.  The sequence is
    padded to a power-of-two length (causal attention leaves earlier
    positions untouched), so few shapes compile."""
    s = _bucket(len(tokens))
    padded = np.zeros(s, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params, jnp.asarray(padded), m)
        for i in range(m.layers):
            x = _layer(x, params["layers"], jnp.int32(i), m, quant)
        return _head(params, x, jnp.asarray(np.asarray(rows, np.int32)), m,
                     quant)


# -- operations and bytes --------------------------------------------------------


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies by in one layer (QKV, O, MLP)."""
    return m.d * m.q_dim + 2 * m.d * m.kv_dim + m.q_dim * m.d + 3 * m.d * m.ff


def token_flops(m: Dims, ctx: np.ndarray) -> np.ndarray:
    """FLOPs of the layers for tokens that attend to ``ctx`` keys each:
    2 per weight, and 4 * heads * head_dim per key (scores and values)."""
    ctx = np.asarray(ctx, np.float64)
    return m.layers * (2.0 * layer_matmul_params(m)
                       + 4.0 * m.heads * m.head_dim * ctx)


def head_flops(m: Dims) -> float:
    """FLOPs of the output head for one token's logits."""
    return 2.0 * m.d * m.vocab


def attention_work(m: Dims, rows: Iterable[np.ndarray]
                   ) -> List[Tuple[int, float, float]]:
    """``[(layers, flops, bytes)]``: every layer is the same kind, and each
    does this paged attention work over one step.

    ``rows``: for each live row, the positions of its valid query tokens.
    A token at position p attends to p + 1 keys; a row reads its keys and
    values once, up to its last valid position, and reads its queries and
    writes its outputs once.
    """
    flops = bytes_ = 0.0
    b = dtype_bytes(m)
    for pos in rows:
        pos = np.asarray(pos, np.float64)
        if not pos.size:
            continue
        flops += 4.0 * m.heads * m.head_dim * float(np.sum(pos + 1))
        bytes_ += 2.0 * m.kv_heads * m.head_dim * (float(pos.max()) + 1) * b
        bytes_ += 2.0 * pos.size * m.heads * m.head_dim * b
    return [(m.layers, flops, bytes_)]
