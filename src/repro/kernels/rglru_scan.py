"""RG-LRU kernel (RecurrentGemma): gated diagonal linear recurrence.

    h_t = a_t * h_{t-1} + x_t

with per-channel, per-step decay ``a_t`` in (0, 1] and ``x_t`` the already
gated+scaled input (sqrt(1 - a_t^2) * i_t * x_t computed by the caller —
keeping the kernel at the recurrence level makes it reusable for any
diagonal SSM).

Grid: (B, T/chunk), time sequential, hidden state [1, D] in VMEM scratch.
The step body is a fused multiply-add over the full channel vector — pure
VPU work with no data-dependent control flow.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(x_ref, a_ref, h_ref, h_final_ref, state_ref, *,
                  chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    def step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)  # [D]
        a_t = a_ref[0, t].astype(jnp.float32)  # [D]
        h = a_t * h + x_t
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_ref[0])
    state_ref[0] = h

    @pl.when(ic == n_chunks - 1)
    def _emit():
        h_final_ref[0] = h.astype(h_final_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(x: jax.Array, a: jax.Array, *, chunk: int = 256,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x, a: [B, T, D].  Returns (h [B, T, D], final_state [B, D])."""
    b, t, d = x.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk

    kernel = functools.partial(_rglru_kernel, chunk=chunk, n_chunks=n_chunks)
    h, h_final = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d), lambda i, c: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        grid=(b, n_chunks),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, a)
    return h, h_final
