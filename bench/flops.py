"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of useful work only: padding rows, padding tokens and the
over-read of a partly filled KV block do not count, so a share of a peak
or a roofline computed from them cannot pass 100% unless the time leaves
out part of the work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

from .weights import Dims


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


def dtype_bytes(m: Dims) -> int:
    return int(np.dtype(m.dtype if m.dtype != "bfloat16" else "float16")
               .itemsize)


def attention_work(m: Dims, rows: Iterable[np.ndarray]) -> Tuple[float, float]:
    """FLOPs and HBM bytes of one layer's paged attention over one step.

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
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
