"""The plain reference: a float32 forward pass of the decoder-only model.

Qwen2 and Llama (Yi) layers as published: RMSNorm, grouped-query
attention with rotary positions (rotate-half, ``rope_theta``), optional
QKV bias, a SwiGLU MLP, a final RMSNorm and the output head (the input
embedding's transpose where the embeddings are tied).  One departure,
stated in the configuration file: the input embedding is multiplied by
``program.embedding_multiplier`` (the served program scales tied
embeddings by sqrt(hidden_size) rounded to bfloat16, as Gemma does; 1
elsewhere).

No kernels, no cache, no batching: one sequence at a time, full causal
attention over it, in float32 at the highest matmul precision, layer by
layer so that it fits the chip beside nothing else.  It imports nothing of
the program; it reads the weights from ``weights.make`` and the seed.

``quant="fp8"`` computes every matrix multiplication of the layers and
the head from float8 (e4m3) operands, weights scaled per output channel
and activations per token: the control that the comparison must refuse.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import Dims

F32 = jnp.float32
Q_BLOCK = 512
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _q8(x, axis):
    """x rounded to e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(_FP8).astype(F32) * s


def _mm(x, w, quant: Optional[str]):
    """x [..., k] @ w [k, n] in float32, or from fp8 operands."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def _bucket(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def logits(params, m: Dims, tokens: np.ndarray, rows: Sequence[int], *,
           quant: Optional[str] = None) -> jax.Array:
    """[len(rows), vocab] float32 logits after ``tokens[: row + 1]``.

    The sequence is padded to a power-of-two length (causal attention
    leaves earlier positions untouched), so few shapes compile."""
    s = _bucket(len(tokens))
    padded = np.zeros(s, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params, jnp.asarray(padded), m)
        for i in range(m.layers):
            x = _layer(x, params["layers"], jnp.int32(i), m, quant)
        return _head(params, x, jnp.asarray(np.asarray(rows, np.int32)), m,
                     quant)


@jax.jit
def _gaps(ref, picked):
    """How far each picked token's reference logit lies below the best."""
    best = jnp.max(ref, -1)
    return best - jnp.take_along_axis(ref, picked[:, None], -1)[:, 0]


def served_gaps(params, m: Dims, prompt: np.ndarray, served: np.ndarray, *,
                control: bool = False) -> Tuple[np.ndarray,
                                                Optional[np.ndarray]]:
    """Gaps of the served tokens under the reference, and with
    ``control``, the gaps of the tokens the fp8 control puts first at the
    same positions (None without)."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    n = len(served)
    seq = np.concatenate([prompt, served[:-1]])
    # rows (and tokens) padded to a power of two by repeating the last,
    # so that few shapes compile
    pad = 1 << (n - 1).bit_length()
    rows = np.arange(len(prompt) - 1, len(seq))
    rows = np.concatenate([rows, np.full(pad - n, rows[-1])])
    picked = np.concatenate([served, np.full(pad - n, served[-1])])
    ref = logits(params, m, seq, rows)
    got = np.asarray(_gaps(ref, jnp.asarray(picked)))[:n]
    if not control:
        return got, None
    low = logits(params, m, seq, rows, quant="fp8")
    ctl = np.asarray(_gaps(ref, jnp.argmax(low, -1).astype(jnp.int32)))[:n]
    return got, ctl


def gaps_of(params, m: Dims, pairs: List[Tuple[np.ndarray, np.ndarray]], *,
            control: bool = False):
    """``served_gaps`` over (prompt, served) pairs; returns the two
    lists of per-token gap arrays."""
    got, ctl = [], []
    for prompt, served in pairs:
        g, c = served_gaps(params, m, prompt, served, control=control)
        got.append(g)
        ctl.append(c)
    return got, ctl
