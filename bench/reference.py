"""The plain reference and the comparison of served tokens with it.

Each family's ``logits`` (``bench/families/<family>.py``) is the forward
pass of its block as published, in float32 at the highest matmul
precision: no kernels, no cache, no batching, one sequence at a time,
full causal attention over it, layer by layer so that it fits the chip
beside nothing else.  It imports nothing of the program; it reads the
weights from the family's ``make`` and the seed.  This module holds what
every family shares: the float32 and fp8 matrix product, RMSNorm, the
padding of a sequence, and the gaps of served tokens.

``quant="fp8"`` computes every matrix multiplication of the layers and
the head from float8 (e4m3) operands, weights scaled per output channel
and activations per token: the control that the comparison must refuse.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def _bucket(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


@jax.jit
def _gaps(ref, picked):
    """How far each picked token's reference logit lies below the best."""
    best = jnp.max(ref, -1)
    return best - jnp.take_along_axis(ref, picked[:, None], -1)[:, 0]


def served_gaps(family, params, m, prompt: np.ndarray,
                served: np.ndarray, *, control: bool = False
                ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Gaps of the served tokens under ``family``'s reference, and with
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
    ref = family.logits(params, m, seq, rows)
    got = np.asarray(_gaps(ref, jnp.asarray(picked)))[:n]
    if not control:
        return got, None
    low = family.logits(params, m, seq, rows, quant="fp8")
    ctl = np.asarray(_gaps(ref, jnp.argmax(low, -1).astype(jnp.int32)))[:n]
    return got, ctl


def gaps_of(family, params, m,
            pairs: List[Tuple[np.ndarray, np.ndarray]], *,
            control: bool = False):
    """``served_gaps`` over (prompt, served) pairs; returns the two
    lists of per-token gap arrays."""
    got, ctl = [], []
    for prompt, served in pairs:
        g, c = served_gaps(family, params, m, prompt, served,
                           control=control)
        got.append(g)
        ctl.append(c)
    return got, ctl
