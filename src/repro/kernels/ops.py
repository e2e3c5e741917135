"""Public jit'd kernel API.

Every op picks the Pallas kernel on TPU and the pure-jnp oracle elsewhere
(overridable with ``impl=``).  Tests call both paths explicitly and assert
allclose; models call these entry points only.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import bebop_decode as _bd
from . import flash_attention as _fa
from . import paged_attention as _pa
from . import ref
from . import rglru_scan as _rg
from . import rwkv6_scan as _rw


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    return "pallas" if _on_tpu() else "reference"


# -- Bebop device decode ------------------------------------------------------


def _page_bytes(pages: jax.Array) -> jax.Array:
    """[N, W] u32 words -> [N, 4W] u8 little-endian bytes, the layout
    the reference decoders read; u8 rows pass through."""
    if pages.dtype == jnp.uint8:
        return pages
    return jax.lax.bitcast_convert_type(pages, jnp.uint8).reshape(
        pages.shape[0], -1)


def decode_column(pages: jax.Array, *, offset: int, count: int,
                  wire_dtype: str, out_dtype=None, block_n: int = 256,
                  impl: Optional[str] = None) -> jax.Array:
    """[N, stride] u8 rows or [N, stride / 4] u32 words -> [N, count]."""
    if _pick(impl) == "pallas":
        return _bd.decode_column(pages, offset=offset, count=count,
                                 wire_dtype=wire_dtype, out_dtype=out_dtype,
                                 block_n=block_n, interpret=not _on_tpu())
    fn = ref.DECODERS[wire_dtype]
    out = fn(_page_bytes(pages), offset, count)
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out


def decode_columns(pages: jax.Array, fields, *, block_n: int = 256,
                   impl: Optional[str] = None):
    """Decode several columns in one pass; fields = ((off, cnt, wd, od), ...)."""
    if _pick(impl) == "pallas":
        return _bd.decode_columns(pages, fields=tuple(fields),
                                  block_n=block_n, interpret=not _on_tpu())
    raw = _page_bytes(pages)
    return [decode_column(raw, offset=off, count=cnt, wire_dtype=wd,
                          out_dtype=od, impl="reference")
            for (off, cnt, wd, od) in fields]


# -- attention ---------------------------------------------------------------


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, q_offset: int = 0,
              block_q: int = 128, block_k: int = 128,
              impl: Optional[str] = None) -> jax.Array:
    if _pick(impl) == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k, q_offset=q_offset,
                                   interpret=not _on_tpu())
    return ref.attention(q, k, v, causal=causal, window=window, scale=scale,
                         q_offset=q_offset)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, qpos: jax.Array, *,
                    scale: Optional[float] = None,
                    impl: Optional[str] = None) -> jax.Array:
    """Attention of new tokens against a block-pooled KV cache.

    q: [B, Hq, T, D]; pools: [N, Hkv, bs, D]; block_tables: [B, M] int32;
    qpos: [B, T] absolute positions of the query tokens.  Pallas serves
    both shapes: the decode kernel for T == 1 and the fused paged-prefill
    kernel for T > 1 (chunked prefill and mixed prefill/decode steps) —
    the whole serving hot loop is fixed-stride block DMAs.
    """
    if _pick(impl) == "pallas":
        if q.shape[2] == 1:
            out = _pa.paged_attention(q[:, :, 0, :], k_pool, v_pool,
                                      block_tables, qpos[:, 0] + 1,
                                      scale=scale, interpret=not _on_tpu())
            return out[:, :, None, :]
        return _pa.paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                           qpos, scale=scale,
                                           interpret=not _on_tpu())
    return ref.paged_attention(q, k_pool, v_pool, block_tables, qpos,
                               scale=scale)


# -- recurrences ---------------------------------------------------------------


def rwkv6(r, k, v, w, u, *, chunk: int = 128,
          impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    if _pick(impl) == "pallas":
        return _rw.rwkv6_scan(r, k, v, w, u, chunk=chunk,
                              interpret=not _on_tpu())
    return ref.rwkv6(r, k, v, w, u)


def rglru(x, a, *, chunk: int = 256,
          impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    if _pick(impl) == "pallas":
        return _rg.rglru_scan(x, a, chunk=chunk, interpret=not _on_tpu())
    return ref.rglru(x, a)
