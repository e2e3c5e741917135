"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref oracle."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bebop_decode import decode_column, decode_columns
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


# --------------------------------------------------------------------------
# bebop_decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,count,block_n", [
    (64, 16, 16), (256, 128, 64), (512, 1, 256), (128, 33, 128),
])
def test_decode_u32_column(rng, n, count, block_n):
    stride = 16 + 4 * count
    pages = rng.integers(0, 255, (n, stride), dtype=np.uint8)
    out = decode_column(jnp.asarray(pages), offset=16, count=count,
                        wire_dtype="uint32", block_n=block_n, interpret=True)
    expect = pages[:, 16:16 + 4 * count].copy().view("<u4")
    assert np.array_equal(np.asarray(out), expect)
    out_ref = ref.bytes_to_u32(jnp.asarray(pages), 16, count)
    assert np.array_equal(np.asarray(out_ref), expect)


@pytest.mark.parametrize("dim", [8, 64, 384])
def test_decode_bf16_column(rng, dim):
    n = 128
    stride = 2 * dim
    vals = rng.standard_normal((n, dim)).astype("<f4")
    raw = (vals.view("<u4") >> 16).astype("<u2")
    pages = raw.view("u1").reshape(n, stride)
    out = decode_column(jnp.asarray(pages), offset=0, count=dim,
                        wire_dtype="bfloat16", interpret=True)
    expect = (raw.astype("<u4") << 16).view("<f4")
    assert np.allclose(np.asarray(out), expect)


@pytest.mark.parametrize("wd,esize", [
    ("float32", 4), ("uint16", 2), ("int32", 4), ("uint8", 1),
    ("float16", 2),
])
def test_decode_column_dtypes(rng, wd, esize):
    n, count = 64, 24
    pages = rng.integers(0, 255, (n, 8 + esize * count), dtype=np.uint8)
    out = np.asarray(decode_column(jnp.asarray(pages), offset=8, count=count,
                                   wire_dtype=wd, interpret=True))
    raw = pages[:, 8:8 + esize * count].copy()
    if wd == "float32":
        assert np.array_equal(out.view("<u4"), raw.view("<f4").view("<u4"))
    elif wd == "int32":
        assert np.array_equal(out, raw.view("<i4"))
    elif wd == "uint16":
        assert np.array_equal(out, raw.view("<u2"))
    elif wd == "uint8":
        assert np.array_equal(out, raw)
    elif wd == "float16":    # bits: NaN payloads and subnormals included
        assert np.array_equal(out.view("<u4"),
                              raw.view("<f2").astype("<f4").view("<u4"))


def test_decode_float16_every_pattern():
    """All 65,536 binary16 patterns widen to numpy's float32 bits."""
    h = np.arange(1 << 16, dtype="<u2").reshape(256, 256)
    out = decode_column(jnp.asarray(h.view("u1")), offset=0, count=256,
                        wire_dtype="float16", interpret=True)
    assert np.array_equal(np.asarray(out).view("<u4"),
                          h.view("<f2").astype("<f4").view("<u4"))


@pytest.mark.parametrize("wd", ["uint16", "bfloat16", "float16", "uint8"])
def test_decode_plane_words_are_normal_floats(rng, wd):
    """Every word XLA moves between the decode's two kernels reads as a
    normal float32: a TPU may move 32-bit words as float32 values, which
    zeroes subnormal patterns and rewrites NaN ones."""
    from repro.kernels.bebop_decode import _SIZE, _planes
    words = rng.integers(0, 1 << 32, (64, 128), dtype=np.uint64)
    words[0, :8] = [0, 1, 0x7F800001, 0xFFC00000, 0x807FFFFF, 0x7F81FFFF,
                    0xFFFFFFFF, 0x00010001]
    size = _SIZE[wd]
    for plane in _planes(jnp.asarray(words.astype(np.uint32)), wd, size,
                         jnp.uint32):
        f = np.abs(np.asarray(plane).view(np.float32))
        assert np.all(np.isfinite(f) & (f >= np.finfo(np.float32).tiny))


def test_decode_multi_column_single_pass(rng):
    n, dim = 128, 32
    stride = 16 + 4 + 2 * dim  # uuid + u32 + bf16[dim] (4-aligned)
    pages = rng.integers(0, 255, (n, stride), dtype=np.uint8)
    outs = decode_columns(jnp.asarray(pages), fields=(
        (0, 16, "uint8", "uint8"),
        (16, 1, "uint32", "int32"),
        (20, dim, "bfloat16", "float32"),
    ), interpret=True)
    assert np.array_equal(np.asarray(outs[0]), pages[:, :16])
    assert np.array_equal(np.asarray(outs[1]).reshape(-1),
                          pages[:, 16:20].copy().view("<u4").reshape(-1)
                          .astype("<i4"))
    raw = pages[:, 20:].copy().view("<u2")
    # random bytes include NaN/Inf bit patterns: compare exact bits
    assert np.array_equal(np.asarray(outs[2]).view("<u4"),
                          raw.astype("<u4") << 16)


def test_device_layout_plan_and_decode(rng):
    """End-to-end: Bebop struct -> page -> device decode == host decode."""
    from repro.core import fastwire, pages as P, types as T
    from repro.core.device import decode_page_device, plan_device_layout
    seq = 32
    s = T.Struct("Ex", [T.Field("doc_id", T.UUID),
                        T.Field("tokens", T.FixedArray(T.UINT32, seq))])
    layout = plan_device_layout(s)
    assert layout.stride == 16 + 4 * seq
    recs = np.zeros(64, dtype=fastwire.static_dtype(s))
    recs["tokens"] = rng.integers(0, 2**31, (64, seq), dtype=np.uint32)
    page = P.write_page("Ex", recs)
    payload = P.read_payload(page, expect_schema="Ex")
    cols = decode_page_device(jnp.asarray(np.ascontiguousarray(payload)),
                              layout, impl="pallas")
    assert np.array_equal(np.asarray(cols["tokens"]),
                          recs["tokens"].astype("<i4"))


def test_misaligned_column_rejected():
    from repro.core import types as T
    from repro.core.device import plan_device_layout
    s = T.Struct("Bad", [T.Field("flag", T.BOOL),
                         T.Field("vals", T.FixedArray(T.UINT32, 4))])
    with pytest.raises(T.SchemaError):
        plan_device_layout(s)


def test_alignment_sort_fixes_layout():
    from repro.core import types as T
    from repro.core.device import plan_device_layout, sort_fields_for_alignment
    s = T.Struct("Bad", [T.Field("flag", T.BOOL),
                         T.Field("vals", T.FixedArray(T.UINT32, 4))])
    fixed = sort_fields_for_alignment(s)
    assert [f.name for f in fixed.fields] == ["vals", "flag"]
    plan_device_layout(fixed)  # no raise


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,t,s,d,causal,window", [
    (2, 4, 2, 128, 128, 64, True, None),
    (1, 8, 1, 128, 128, 32, True, None),     # MQA
    (2, 4, 4, 64, 128, 64, False, None),     # cross-ish
    (1, 4, 2, 128, 128, 64, True, 64),       # sliding window
    (1, 2, 2, 64, 64, 128, True, None),
])
def test_flash_attention_vs_ref(rng, b, hq, hkv, t, s, d, causal, window):
    q = rng.standard_normal((b, hq, t, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal, window=window, block_q=64,
                         block_k=64, interpret=True)
    o2 = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=3e-5, rtol=1e-4)


def test_flash_attention_decode_q1(rng):
    """Decode step: q length 1 against a 256-long KV history."""
    q = rng.standard_normal((2, 4, 1, 64)).astype(np.float32)
    k = rng.standard_normal((2, 2, 256, 64)).astype(np.float32)
    v = rng.standard_normal((2, 2, 256, 64)).astype(np.float32)
    o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True, q_offset=255, block_q=1, block_k=64,
                         interpret=True)
    o2 = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       causal=True, q_offset=255)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5,
                               rtol=1e-4)


# --------------------------------------------------------------------------
# paged attention (block-table KV gather)
# --------------------------------------------------------------------------

def _paged_setup(rng, b, hq, hkv, d, bs, m, n):
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    kp = rng.standard_normal((n, hkv, bs, d)).astype(np.float32)
    vp = rng.standard_normal((n, hkv, bs, d)).astype(np.float32)
    # distinct physical blocks per row, shuffled: the table is the ONLY
    # thing mapping logical order onto the pool
    tables = np.stack([rng.permutation(np.arange(1, n))[:m]
                       for _ in range(b)]).astype(np.int32)
    return q, kp, vp, tables


@pytest.mark.parametrize("b,hq,hkv,d,bs,m,n", [
    (4, 4, 2, 16, 8, 6, 32),
    (2, 8, 1, 64, 16, 4, 16),     # MQA
    (3, 4, 4, 32, 16, 8, 64),
    (1, 2, 2, 128, 32, 2, 8),
])
def test_paged_attention_vs_ref(rng, b, hq, hkv, d, bs, m, n):
    q, kp, vp, tables = _paged_setup(rng, b, hq, hkv, d, bs, m, n)
    ctx = rng.integers(1, m * bs + 1, b).astype(np.int32)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(tables), jnp.asarray(ctx),
                          interpret=True)
    expect = ref.paged_attention(jnp.asarray(q)[:, :, None, :],
                                 jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(tables),
                                 jnp.asarray(ctx - 1)[:, None])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(expect)[:, :, 0, :],
                               atol=3e-5, rtol=1e-4)


def test_paged_attention_matches_contiguous(rng):
    """Gathering through the block table == dense attention over the
    contiguous cache the table describes (per row, per context length)."""
    b, hq, hkv, d, bs, m, n = 4, 4, 2, 32, 8, 4, 32
    q, kp, vp, tables = _paged_setup(rng, b, hq, hkv, d, bs, m, n)
    ctx = np.array([1, 9, 17, 32], np.int32)
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx), interpret=True))
    k = np.moveaxis(kp[tables], 2, 1).reshape(b, hkv, m * bs, d)
    v = np.moveaxis(vp[tables], 2, 1).reshape(b, hkv, m * bs, d)
    for i in range(b):
        dense = ref.attention(
            jnp.asarray(q[i:i + 1, :, None, :]),
            jnp.asarray(k[i:i + 1, :, :ctx[i]]),
            jnp.asarray(v[i:i + 1, :, :ctx[i]]),
            causal=True, q_offset=int(ctx[i]) - 1)
        np.testing.assert_allclose(out[i], np.asarray(dense)[0, :, 0],
                                   atol=3e-5, rtol=1e-4)


def test_paged_attention_ignores_unlisted_blocks(rng):
    """Pool contents outside a row's table must never leak into its
    output: scribbling over every unlisted block changes nothing."""
    b, hq, hkv, d, bs, m, n = 2, 4, 2, 16, 8, 4, 32
    q, kp, vp, tables = _paged_setup(rng, b, hq, hkv, d, bs, m, n)
    ctx = np.array([13, 29], np.int32)
    args = (jnp.asarray(tables), jnp.asarray(ctx))
    out1 = np.asarray(paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                      jnp.asarray(vp), *args,
                                      interpret=True))
    listed = set(tables.reshape(-1).tolist())
    scrib_k, scrib_v = kp.copy(), vp.copy()
    for blk in range(n):
        if blk not in listed:
            scrib_k[blk] = 1e3
            scrib_v[blk] = -1e3
    out2 = np.asarray(paged_attention(jnp.asarray(q), jnp.asarray(scrib_k),
                                      jnp.asarray(scrib_v), *args,
                                      interpret=True))
    np.testing.assert_array_equal(out1, out2)


def test_paged_ref_prefill_chunk_shape(rng):
    """The reference path also serves chunked prefill (T > 1)."""
    b, hq, hkv, d, bs, m, n, t = 2, 4, 2, 16, 8, 4, 16, 8
    q = rng.standard_normal((b, hq, t, d)).astype(np.float32)
    _, kp, vp, tables = _paged_setup(rng, b, hq, hkv, d, bs, m, n)
    qpos = np.broadcast_to(np.arange(t, dtype=np.int32), (b, t))
    out = ref.paged_attention(jnp.asarray(q), jnp.asarray(kp),
                              jnp.asarray(vp), jnp.asarray(tables),
                              jnp.asarray(qpos))
    assert out.shape == (b, hq, t, d)
    assert np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------
# paged prefill attention (multi-token query tiles through the block table)
# --------------------------------------------------------------------------

def _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t):
    q = rng.standard_normal((b, hq, t, d)).astype(np.float32)
    _, kp, vp, tables = _paged_setup(rng, b, hq, hkv, d, bs, m, n)
    return q, kp, vp, tables


@pytest.mark.parametrize("b,hq,hkv,d,bs,m,n,t", [
    (3, 4, 2, 16, 8, 4, 32, 8),
    (2, 8, 1, 64, 16, 4, 16, 16),   # MQA
    (1, 2, 2, 128, 32, 2, 8, 4),
    (2, 4, 4, 32, 16, 8, 64, 32),
])
def test_paged_prefill_vs_ref(rng, b, hq, hkv, d, bs, m, n, t):
    """Chunk tiles at per-row start offsets: kernel == reference gather."""
    q, kp, vp, tables = _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t)
    starts = rng.integers(0, m * bs - t + 1, b)
    qpos = (starts[:, None] + np.arange(t)).astype(np.int32)
    out = paged_prefill_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(tables),
                                  jnp.asarray(qpos), interpret=True)
    expect = ref.paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(tables),
                                 jnp.asarray(qpos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=1e-4)


def test_paged_prefill_mixed_rows_vs_ref(rng):
    """The mixed-step shape: decode rows padded to the chunk width with
    repeated positions alongside genuinely prefilling rows — one call."""
    b, hq, hkv, d, bs, m, n, t = 4, 4, 2, 16, 8, 4, 32, 8
    q, kp, vp, tables = _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t)
    qpos = np.stack([
        np.full(t, 19),            # decode row, ctx 20, t-1 pad duplicates
        5 + np.arange(t),          # prefill chunk at offset 5
        np.full(t, 0),             # decode row at the very first position
        np.arange(t),              # prefill chunk from position 0
    ]).astype(np.int32)
    out = paged_prefill_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(tables),
                                  jnp.asarray(qpos), interpret=True)
    expect = ref.paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(tables),
                                 jnp.asarray(qpos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=1e-4)
    # a padded decode row agrees with the T == 1 decode kernel at token 0
    dec = paged_attention(jnp.asarray(q[:1, :, 0, :]), jnp.asarray(kp),
                          jnp.asarray(vp), jnp.asarray(tables[:1]),
                          jnp.asarray(np.array([20], np.int32)),
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out)[0, :, 0, :],
                               np.asarray(dec)[0], atol=3e-5, rtol=1e-4)


def test_paged_prefill_matches_flash_on_contiguous(rng):
    """Gathering chunk tiles through the block table == flash attention
    over the contiguous cache the table describes (per row)."""
    b, hq, hkv, d, bs, m, n, t = 3, 4, 2, 32, 8, 4, 32, 8
    q, kp, vp, tables = _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t)
    ctx = np.array([16, 24, 32], np.int32)       # history INCLUDING chunk
    qpos = (ctx[:, None] - t + np.arange(t)).astype(np.int32)
    out = np.asarray(paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(qpos), interpret=True))
    k = np.moveaxis(kp[tables], 2, 1).reshape(b, hkv, m * bs, d)
    v = np.moveaxis(vp[tables], 2, 1).reshape(b, hkv, m * bs, d)
    for i in range(b):
        flash = flash_attention(
            jnp.asarray(q[i:i + 1]), jnp.asarray(k[i:i + 1, :, :ctx[i]]),
            jnp.asarray(v[i:i + 1, :, :ctx[i]]), causal=True,
            q_offset=int(ctx[i]) - t, block_q=t, block_k=bs,
            interpret=True)
        np.testing.assert_allclose(out[i], np.asarray(flash)[0],
                                   atol=3e-5, rtol=1e-4)


def test_paged_prefill_ignores_unlisted_blocks(rng):
    """Same isolation contract as decode: scribbling over every block not
    listed in a row's table changes nothing."""
    b, hq, hkv, d, bs, m, n, t = 2, 4, 2, 16, 8, 4, 32, 8
    q, kp, vp, tables = _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t)
    qpos = np.stack([3 + np.arange(t), 11 + np.arange(t)]).astype(np.int32)
    args = (jnp.asarray(tables), jnp.asarray(qpos))
    out1 = np.asarray(paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), *args,
        interpret=True))
    listed = set(tables.reshape(-1).tolist())
    scrib_k, scrib_v = kp.copy(), vp.copy()
    for blk in range(n):
        if blk not in listed:
            scrib_k[blk] = 1e3
            scrib_v[blk] = -1e3
    out2 = np.asarray(paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(scrib_k), jnp.asarray(scrib_v), *args,
        interpret=True))
    np.testing.assert_array_equal(out1, out2)


def test_ops_paged_dispatch_prefill_pallas(rng):
    """ops.paged_attention T > 1 runs the Pallas prefill kernel (no more
    reference fallback) and agrees with the reference path."""
    from repro.kernels import ops
    b, hq, hkv, d, bs, m, n, t = 2, 4, 2, 16, 8, 4, 16, 8
    q, kp, vp, tables = _prefill_setup(rng, b, hq, hkv, d, bs, m, n, t)
    qpos = np.stack([np.arange(t), 7 + np.arange(t)]).astype(np.int32)
    args = tuple(map(jnp.asarray, (q, kp, vp, tables, qpos)))
    out_pl = ops.paged_attention(*args, impl="pallas")
    out_ref = ops.paged_attention(*args, impl="reference")
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-4)


def test_flash_attention_bf16(rng):
    q = rng.standard_normal((1, 2, 64, 64)).astype(jnp.bfloat16)
    k = rng.standard_normal((1, 2, 64, 64)).astype(jnp.bfloat16)
    v = rng.standard_normal((1, 2, 64, 64)).astype(jnp.bfloat16)
    o1 = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    o2 = ref.attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, dtype=np.float32),
                               np.asarray(o2, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)


# --------------------------------------------------------------------------
# rwkv6
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,t,kk,vv,chunk", [
    (1, 1, 32, 16, 16, 8),
    (2, 2, 64, 32, 32, 16),
    (1, 4, 128, 64, 64, 64),
])
def test_rwkv6_vs_ref(rng, b, h, t, kk, vv, chunk):
    r = rng.standard_normal((b, h, t, kk)).astype(np.float32) * 0.5
    k = rng.standard_normal((b, h, t, kk)).astype(np.float32) * 0.5
    v = rng.standard_normal((b, h, t, vv)).astype(np.float32) * 0.5
    w = np.exp(-np.exp(rng.standard_normal((b, h, t, kk)))).astype(np.float32)
    u = (rng.standard_normal((h, kk)) * 0.3).astype(np.float32)
    o1, s1 = rwkv6_scan(*map(jnp.asarray, (r, k, v, w, u)), chunk=chunk,
                        interpret=True)
    o2, s2 = ref.rwkv6(*map(jnp.asarray, (r, k, v, w, u)))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_rwkv6_state_continuity(rng):
    """Scanning two halves with carried state == one full scan."""
    b, h, t, d = 1, 2, 64, 32
    r, k, w = (rng.standard_normal((b, h, t, d)).astype(np.float32) * 0.4
               for _ in range(3))
    w = np.exp(-np.exp(w))
    v = rng.standard_normal((b, h, t, d)).astype(np.float32) * 0.4
    u = (rng.standard_normal((h, d)) * 0.3).astype(np.float32)
    o_full, s_full = ref.rwkv6(*map(jnp.asarray, (r, k, v, w, u)))
    o1, s1 = ref.rwkv6(r[:, :, :32], k[:, :, :32], v[:, :, :32],
                       w[:, :, :32], u)
    o2, s2 = ref.rwkv6(r[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                       w[:, :, 32:], u, initial_state=s1)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(o1), np.asarray(o2)], axis=2),
        np.asarray(o_full), atol=1e-4)


# --------------------------------------------------------------------------
# rg-lru
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d,chunk", [
    (1, 32, 16, 8), (2, 128, 64, 32), (1, 256, 128, 256),
])
def test_rglru_vs_ref(rng, b, t, d, chunk):
    x = rng.standard_normal((b, t, d)).astype(np.float32)
    a = 1.0 / (1.0 + np.exp(-rng.standard_normal((b, t, d)))).astype(
        np.float32)
    h1, f1 = rglru_scan(jnp.asarray(x), jnp.asarray(a), chunk=chunk,
                        interpret=True)
    h2, f2 = ref.rglru(jnp.asarray(x), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-5)


def test_rglru_decay_bounds(rng):
    """With a == 1 the state is a running sum; with a == 0 it's identity."""
    x = rng.standard_normal((1, 16, 8)).astype(np.float32)
    ones = np.ones_like(x)
    h_sum, _ = ref.rglru(jnp.asarray(x), jnp.asarray(ones))
    np.testing.assert_allclose(np.asarray(h_sum), np.cumsum(x, axis=1),
                               atol=1e-5)
    h_id, _ = ref.rglru(jnp.asarray(x), jnp.asarray(np.zeros_like(x)))
    np.testing.assert_allclose(np.asarray(h_id), x, atol=1e-6)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_rwkv6_chunked_matches_sequential(rng, chunk):
    """The §Perf chunked WKV reformulation is numerically equivalent."""
    B, H, T, K, V = 2, 2, 128, 32, 32
    r = rng.standard_normal((B, H, T, K)).astype(np.float32) * 0.5
    k = rng.standard_normal((B, H, T, K)).astype(np.float32) * 0.5
    v = rng.standard_normal((B, H, T, V)).astype(np.float32) * 0.5
    wlog = rng.uniform(-6, 0.5, (B, H, T, K)).astype(np.float32)
    w = np.exp(-np.exp(wlog))
    u = (rng.standard_normal((H, K)) * 0.3).astype(np.float32)
    o1, s1 = ref.rwkv6(*map(jnp.asarray, (r, k, v, w, u)))
    o2, s2 = ref.rwkv6_chunked(*map(jnp.asarray, (r, k, v, w, u)),
                               chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-3)


def test_rwkv6_chunked_model_loss_matches(rng):
    """Full model: chunked impl gives the same loss as sequential."""
    import dataclasses
    import jax as _jax
    from repro.configs import get_config, reduced_config
    from repro.models import get_model
    cfg = reduced_config(get_config("rwkv6-7b"))
    cfg_seq = dataclasses.replace(cfg, rwkv_impl="sequential")
    cfg_chk = dataclasses.replace(cfg, rwkv_impl="chunked", rwkv_chunk=8)
    m1, m2 = get_model(cfg_seq), get_model(cfg_chk)
    params = m1.init(_jax.random.PRNGKey(0))
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (2, 16))
             .astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size, (2, 16))
             .astype(np.int32)}
    l1 = float(m1.loss(params, batch))
    l2 = float(m2.loss(params, batch))
    assert abs(l1 - l2) < 1e-3, (l1, l2)
