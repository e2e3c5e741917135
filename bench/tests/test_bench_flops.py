"""The operation, byte, roofline and MFU arithmetic against hand counts."""
import numpy as np
import pytest

from bench import flops, spec, weights
from bench.tests import tiny

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _dims(**kw):
    base = dict(layers=2, d=8, heads=4, kv_heads=2, head_dim=2, ff=16,
                vocab=32, qkv_bias=False, tied=True, eps=1e-6, theta=1e4,
                embed_mult=1.0, dtype="bfloat16")
    base.update(kw)
    return weights.Dims(**base)


def test_layer_params_and_token_flops():
    m = _dims()
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    assert flops.layer_matmul_params(m) == 64 + 32 + 32 + 64 + 384
    # 2 layers x (2 x 576 + 4 x 4 heads x 2 x ctx)
    assert flops.token_flops(m, 3) == 2 * (2 * 576 + 32 * 3)
    assert flops.head_flops(m) == 2 * 8 * 32


def test_attention_work_counts_valid_tokens_only():
    m = _dims()
    f, b = flops.attention_work(m, [np.array([4, 5]), np.array([0])])
    # scores and values: 4 x heads x head_dim per key; keys 5 + 6 + 1
    assert f == 4 * 4 * 2 * (5 + 6 + 1)
    # K and V up to the last position, then Q in and O out, 2 B each
    kv = 2 * 2 * 2 * (6 + 1) * 2
    qo = 2 * (2 + 1) * 4 * 2 * 2
    assert b == kv + qo
    assert flops.roofline_seconds(f, b, PEAK) == max(f / 100.0, b / 10.0)


class _Ctx:
    def __init__(self, steps, counters, kernel_ns, window_s=1.0):
        self.dims = _dims()
        self.peak = PEAK
        self.steps = steps
        self.counters = counters
        self.window_s = window_s
        self._ns = kernel_ns

    def reduced(self, patterns=None):
        class R:
            kernel_ns = {k: self._ns for k in (patterns or {})}
            window_ns = 1e9
            busy_ns = 0.25e9
        return R


def _read(name, ctx):
    cell = tiny.load()
    return cell.reader(name)(ctx)


def test_step_mfu_and_attn_roofline_by_hand():
    # one mixed step: row 0 prefills 3 tokens at 0..2, row 1 decodes at
    # position 9, row 2 is idle (null block)
    pos = np.array([[0, 1, 2, 2], [9, 9, 9, 9], [0, 0, 0, 0]])
    steps = [("mixed", np.array([5, 7, 0]), pos, np.array([2, 0, 0]))]
    m = _dims()
    counters = {"batched_rows": 1.0, "spec_accepted": 0.0}
    ctx = _Ctx(steps, counters, kernel_ns=2e9)
    want = (sum(flops.token_flops(m, c) for c in (1, 2, 3, 10))
            + flops.head_flops(m))
    assert _read("step_mfu", ctx) == pytest.approx(100 * want / 100.0)
    f, b = flops.attention_work(m, [np.array([0, 1, 2]), np.array([9])])
    need = m.layers * max(f / 100.0, b / 10.0)
    assert _read("attn_roofline", ctx) == pytest.approx(100 * need / 2.0)
    assert _read("idle_share", ctx) == pytest.approx(75.0)


def test_readers_return_nothing_without_something_to_read():
    ctx = _Ctx([], {"batched_rows": 0.0, "decode_steps": 0.0}, kernel_ns=0)
    for name in ("step_mfu", "attn_roofline", "rows_per_step"):
        assert _read(name, ctx) is None


def test_peaks_are_keyed_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
