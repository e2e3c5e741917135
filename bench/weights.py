"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the program is handed
them, and the reference makes the same ones again from the same seed with
the same function, so it takes nothing the program made.  They are made
in the type they are served in (the configuration's dtype), in the
program's layout: layer parameters stacked on a leading axis.

Scales keep activations of order one at any width: matrices are normal
with standard deviation ``fan_in ** -0.5``, norm weights ``1 + 0.1 N(0, 1)``
and biases ``0.1 N(0, 1)``, so that the norms and the biases change the
result and a path that drops them shows.  The input embedding is the
published ``initializer_range`` of both families, 0.02, divided by the
program's embedding multiplier, so that the first layer sees embeddings of
the published scale.  (With the multiplier on top of 0.02, the input token
dominates the residual stream, greedy output repeats it, and no rounding,
not even fp8's, changes a served token.)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def key_of(seed: int) -> jax.Array:
    """A threefry key from any whole number (beyond 32 bits too)."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


def _layer(key, m: Dims):
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
         "layers": jax.vmap(lambda k: _layer(k, m))(
             jax.random.split(k_layers, m.layers)),
         "final_norm": (1 + _normal(k_norm, (m.d,), 0.1, jnp.float32)
                        ).astype(dt)}
    if not m.tied:
        p["lm_head"] = _normal(k_head, (m.d, m.vocab), m.d ** -0.5, dt)
    return p
