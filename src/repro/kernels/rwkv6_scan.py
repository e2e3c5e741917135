"""RWKV6 (Finch) WKV kernel: linear attention with data-dependent decay.

Per head, the recurrence over a [K, V] state matrix S:

    o_t = (S_{t-1} + diag(u) k_t v_t^T)^T r_t
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

Grid: (B*H, T/chunk) with the time axis sequential; S lives in VMEM scratch
and is carried across chunks.  Within a chunk the step loop is a
``fori_loop`` whose body is pure [K, V] vector algebra (outer product,
row-scale, reduce) — no data-dependent branches, MXU/VPU friendly.

The data-dependent decay ``w_t`` is exactly why this architecture needs a
custom kernel: XLA cannot fuse the per-step diagonal rescale into a matmul
chain, but expressed blockwise in VMEM the whole chunk stays on-chip.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)  # [K]

    def step(t, S):
        r_t = r_ref[0, t].astype(jnp.float32)   # [K]
        k_t = k_ref[0, t].astype(jnp.float32)   # [K]
        v_t = v_ref[0, t].astype(jnp.float32)   # [V]
        w_t = w_ref[0, t].astype(jnp.float32)   # [K]
        kv = k_t[:, None] * v_t[None, :]        # [K, V]
        att = S + u[:, None] * kv               # [K, V]
        o_t = jnp.sum(r_t[:, None] * att, axis=0)  # [V]
        o_ref[0, t] = o_t.astype(o_ref.dtype)
        return w_t[:, None] * S + kv

    S = jax.lax.fori_loop(0, chunk, step, state_ref[...])
    state_ref[...] = S

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        s_final_ref[0] = S.astype(s_final_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = 128,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """r,k,w: [B,H,T,K]; v: [B,H,T,V]; u: [H,K].

    Returns (out [B,H,T,V], final_state [B,H,K,V]).
    """
    b, h, t, kk = r.shape
    vv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    bh = b * h

    r2 = r.reshape(bh, t, kk)
    k2 = k.reshape(bh, t, kk)
    v2 = v.reshape(bh, t, vv)
    w2 = w.reshape(bh, t, kk)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    out, s_final = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, vv), v.dtype),
            jax.ShapeDtypeStruct((bh, kk, vv), jnp.float32),
        ],
        in_specs=[
            pl.BlockSpec((1, chunk, kk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, vv), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, kk), lambda i, c: (i % h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, vv), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, kk, vv), lambda i, c: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        grid=(bh, n_chunks),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r2, k2, v2, w2, u)
    return out.reshape(b, h, t, vv), s_final.reshape(b, h, kk, vv)
