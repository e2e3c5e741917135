"""Operations and bytes the algorithm needs, computed from shapes.

What depends on the block (model FLOPs a token, the attention work of
each kind of layer) is its family's (``bench/families/<family>.py``:
``token_flops``, ``head_flops``, ``attention_work``); what every family
shares is here.  Counts are of useful work only: padding rows, padding
tokens and the over-read of a partly filled KV block do not count, so a
share of a peak or a roofline computed from them cannot pass 100% unless
the time leaves out part of the work.
"""
from __future__ import annotations

import numpy as np


def dtype_bytes(m) -> int:
    """Bytes of one element of ``m.dtype``."""
    return int(np.dtype(m.dtype if m.dtype != "bfloat16" else "float16")
               .itemsize)


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
