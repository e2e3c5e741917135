"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + finite values; decode paths for every family.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.models import get_model


def _batch_for(cfg, b, t, rng):
    if cfg.input_kind == "embeddings":
        return {"embeds": rng.standard_normal((b, t, cfg.d_model))
                .astype(np.float32),
                "positions": np.broadcast_to(np.arange(t), (3, b, t))
                .astype(np.int32),
                "labels": rng.integers(0, cfg.vocab_size, (b, t))
                .astype(np.int32)}
    if cfg.input_kind == "frames":
        return {"frames": rng.standard_normal(
            (b, max(t // cfg.frame_ratio, 1), cfg.d_model))
            .astype(np.float32),
            "tokens": rng.integers(0, cfg.vocab_size, (b, t))
            .astype(np.int32),
            "labels": rng.integers(0, cfg.vocab_size, (b, t))
            .astype(np.int32)}
    return {"tokens": rng.integers(0, cfg.vocab_size, (b, t))
            .astype(np.int32),
            "labels": rng.integers(0, cfg.vocab_size, (b, t))
            .astype(np.int32)}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_grad(arch, rng):
    cfg = reduced_config(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, t = 2, 16
    batch = _batch_for(cfg, b, t, rng)
    logits = jax.jit(model.logits)(params, batch)
    assert logits.shape == (b, t, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss))
    gn = jax.tree.reduce(
        lambda a, bb: a + bb,
        jax.tree.map(lambda g: float(jnp.sum(jnp.abs(
            g.astype(jnp.float32)))), grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_config(a).input_kind != "embeddings"])
def test_smoke_decode_path(arch, rng):
    """prefill + N decode steps; cache shapes stable, logits finite."""
    cfg = reduced_config(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, t, cache_len = 2, 8, 32
    batch = _batch_for(cfg, b, t, rng)
    batch.pop("labels")
    logits, cache = jax.jit(
        lambda p, bt: model.prefill(p, bt, cache_len))(params, batch)
    assert logits.shape == (b, cfg.vocab_size)
    shapes0 = jax.tree.map(lambda a: a.shape, cache)
    tok = rng.integers(0, cfg.vocab_size, (b, 1)).astype(np.int32)
    step = jax.jit(model.decode_step)
    for i in range(3):
        logits, cache = step(params, tok, cache, jnp.int32(t + i))
        assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert jax.tree.map(lambda a: a.shape, cache) == shapes0


def test_prefill_decode_consistency(rng):
    """Greedy next-token from (prefill then decode) == full forward argmax."""
    cfg = reduced_config(get_config("qwen2-1.5b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, t = 1, 12
    toks = rng.integers(0, cfg.vocab_size, (b, t + 1)).astype(np.int32)
    # full forward logits at position t-1 predict token t
    full = model.logits(params, {"tokens": toks[:, :t]})
    logits_prefill, cache = model.prefill(params, {"tokens": toks[:, :t]},
                                          cache_len=32)
    np.testing.assert_allclose(np.asarray(full[:, -1]),
                               np.asarray(logits_prefill), atol=2e-2,
                               rtol=2e-2)
    # decode one more token and compare with forward over t+1
    full2 = model.logits(params, {"tokens": toks})
    logits_dec, _ = model.decode_step(params, toks[:, t:t + 1], cache,
                                      jnp.int32(t))
    np.testing.assert_allclose(np.asarray(full2[:, -1]),
                               np.asarray(logits_dec), atol=2e-2, rtol=2e-2)


def test_paged_step_verify_matches_sequential_steps(rng):
    """The speculative verifier's per-position logits == what sequential
    one-token paged steps produce at the same positions (same pool
    content, same masks) — the property that makes draft acceptance
    equivalent to running the serial loop."""
    from repro.serving import PagedKVCache

    cfg = reduced_config(get_config("qwen2-1.5b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    t = 6
    toks = rng.integers(0, cfg.vocab_size, (1, t)).astype(np.int32)

    def fresh():
        cache = PagedKVCache(num_layers=cfg.num_layers,
                             num_kv_heads=cfg.num_kv_heads,
                             head_dim=cfg.head_dim, cache_len=64,
                             block_size=16, max_concurrent=1,
                             dtype=cfg.dtype, prefix_cache=False)
        cache.pool = model.init_paged_pool(cache.layout.num_blocks,
                                           cache.block_size)
        table = jnp.asarray(cache.allocate(0, 64)[None, :])
        return cache, table

    # sequential: t one-token steps, logits after consuming tokens 0..j
    cache, table = fresh()
    seq_logits = []
    for j in range(t):
        logits, cache.pool = model.paged_step(
            params, jnp.asarray(toks[:, j:j + 1]), cache.pool, table,
            jnp.full((1, 1), j, jnp.int32), jnp.zeros((1,), jnp.int32))
        seq_logits.append(np.asarray(logits))
    # verify: ONE call over all t tokens, logits at every position
    cache, table = fresh()
    ver_logits, _ = model.paged_step_verify(
        params, jnp.asarray(toks), cache.pool, table,
        jnp.arange(t, dtype=jnp.int32)[None, :],
        jnp.full((1,), t - 1, jnp.int32))
    ver_logits = np.asarray(ver_logits)
    assert ver_logits.shape == (1, t, cfg.vocab_size)
    for j in range(t):
        np.testing.assert_allclose(ver_logits[:, j], seq_logits[j],
                                   atol=1e-4, rtol=1e-4)
        assert ver_logits[:, j].argmax(-1) == seq_logits[j].argmax(-1)


def _mixed_step_inputs(cfg, model, rng):
    """A mixed paged step over a pool of random contents: a prompt chunk
    from position 0, a decode row at 20, a 5-token chunk at 16 (C = 8).
    Returns (params, pool, step args, the valid (row, token) pairs)."""
    params = model.init(jax.random.PRNGKey(3))
    n, bs, m, c = 16, 8, 4, 8
    shape = (cfg.num_layers, n, cfg.num_kv_heads, bs, cfg.head_dim)
    pool = {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}
    tables = np.array([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                      np.int32)
    starts, last = np.array([0, 20, 16]), np.array([7, 0, 4], np.int32)
    positions = (starts[:, None] + np.minimum(np.arange(c), last[:, None])
                 ).astype(np.int32)
    toks = rng.integers(0, cfg.vocab_size, (3, c)).astype(np.int32)
    args = tuple(map(jnp.asarray, (toks, tables, positions, last)))
    valid = [(b, j) for b in range(3) for j in range(last[b] + 1)]
    return params, pool, args, valid


def _paged_layers_cfg():
    return dataclasses.replace(reduced_config(get_config("qwen2-1.5b")),
                               num_layers=3)


def test_paged_step_writes_only_valid_rows(rng):
    """One mixed paged step changes the pool only at the (layer, block,
    KV head, slot) rows of its valid tokens, and in the null block where
    the padding goes; every valid token's row is written in every layer."""
    cfg = _paged_layers_cfg()
    model = get_model(cfg)
    params, pool, (toks, tables, positions, last), valid = \
        _mixed_step_inputs(cfg, model, rng)
    before = {k: np.asarray(v) for k, v in pool.items()}
    _, new = jax.jit(model.paged_step)(params, toks, pool, tables,
                                       positions, last)
    bs = before["k"].shape[3]
    want = np.zeros(before["k"].shape[:4], bool)   # [L, N, Hkv, bs]
    for b, j in valid:
        p = int(positions[b, j])
        want[:, int(tables[b, p // bs]), :, p % bs] = True
    for name in ("k", "v"):
        changed = (np.asarray(new[name]) != before[name]).any(-1)
        changed[:, 0] = False                       # the null block
        np.testing.assert_array_equal(changed, want)


def test_paged_step_matches_per_layer_slices(rng):
    """The in-place step == the layer loop it replaced: each layer's
    [N, Hkv, bs, hd] slice taken out of the pool, written with one
    [Hkv, hd] window per token, attended and stacked back.  Logits and
    the pool agree."""
    from repro.kernels import ref
    from repro.models import layers as L

    cfg = _paged_layers_cfg()
    model = get_model(cfg)
    params, pool, (toks, tables, positions, last), _ = \
        _mixed_step_inputs(cfg, model, rng)
    logits, new = jax.jit(model.paged_step)(params, toks, pool, tables,
                                            positions, last)

    def sliced_step(params, pool):
        b, c = toks.shape
        x = L.embed(params, toks, cfg)
        bs = pool["k"].shape[3]
        pos = jnp.clip(positions, 0, tables.shape[1] * bs - 1)
        ok = jnp.arange(c)[None, :] <= last[:, None]
        blk = jnp.where(ok, jnp.take_along_axis(tables, pos // bs, 1), 0)
        slot = jnp.where(ok, pos % bs, 0).reshape(-1)
        ks, vs = [], []
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = L._project_qkv(p["attn"], h, cfg)
            q, k = L._pos_embed(q, k, cfg, positions)
            k_l = pool["k"][i].at[blk.reshape(-1), :, slot, :].set(
                jnp.moveaxis(k, 1, 2).reshape(b * c, cfg.num_kv_heads, -1))
            v_l = pool["v"][i].at[blk.reshape(-1), :, slot, :].set(
                jnp.moveaxis(v, 1, 2).reshape(b * c, cfg.num_kv_heads, -1))
            o = ref.paged_attention(q, k_l[None], v_l[None], tables, pos, 0)
            x = x + o.transpose(0, 2, 1, 3).reshape(b, c, cfg.q_dim) \
                @ p["attn"]["wo"]
            x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps),
                          cfg)
            ks.append(k_l)
            vs.append(v_l)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        return (L.unembed(params, x_last, cfg)[:, 0],
                {"k": jnp.stack(ks), "v": jnp.stack(vs)})

    want_logits, want = jax.jit(sliced_step)(params, pool)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=1e-5, rtol=1e-5)
    for name in ("k", "v"):
        got, exp = np.asarray(new[name]), np.asarray(want[name])
        # the null block takes the padding's writes in no fixed order
        np.testing.assert_allclose(got[:, 1:], exp[:, 1:], atol=1e-5,
                                   rtol=1e-5)


def test_rwkv_decode_matches_forward(rng):
    """RWKV state decode == full-sequence forward (stronger check: exact
    recurrence)."""
    cfg = reduced_config(get_config("rwkv6-7b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, t = 1, 10
    toks = rng.integers(0, cfg.vocab_size, (b, t + 1)).astype(np.int32)
    full = model.logits(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :t]}, cache_len=0)
    logits_dec, _ = model.decode_step(params, toks[:, t:t + 1], cache,
                                      jnp.int32(t))
    np.testing.assert_allclose(np.asarray(full[:, -1]),
                               np.asarray(logits_dec), atol=2e-2, rtol=2e-2)


def test_local_window_attention_masks(rng):
    """recurrentgemma window: token t must not see tokens < t-window+1."""
    from repro.kernels import ref
    q = rng.standard_normal((1, 1, 8, 4)).astype(np.float32)
    k = rng.standard_normal((1, 1, 8, 4)).astype(np.float32)
    v = np.eye(8, 4, dtype=np.float32)[None, None]
    out_w2 = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           causal=True, window=2)
    # with window=2, position 7 attends only to {6, 7}: rows of v beyond
    # are unreachable
    probsless = np.asarray(out_w2)[0, 0, 7]
    full = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True)
    assert not np.allclose(probsless, np.asarray(full)[0, 0, 7])


def test_moe_aux_loss_and_flops_scaling(rng):
    cfg = reduced_config(get_config("qwen2-moe-a2.7b"))
    from repro.models.moe import init_moe, moe_ffn
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)),
                    dtype=jnp.float32)
    out, aux = moe_ffn(p, x, cfg)
    assert out.shape == x.shape
    assert float(aux) > 0
    assert np.isfinite(np.asarray(out)).all()


def test_moe_capacity_drops_dont_nan(rng):
    """Tiny capacity factor forces drops; output must stay finite."""
    import dataclasses
    cfg = reduced_config(get_config("granite-moe-1b-a400m"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    from repro.models.moe import init_moe, moe_ffn
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)),
                    dtype=jnp.float32)
    out, aux = moe_ffn(p, x, cfg)
    assert np.isfinite(np.asarray(out)).all()


def test_mrope_sections_rotate_by_different_axes(rng):
    from repro.models.layers import apply_mrope
    x = rng.standard_normal((1, 1, 4, 16)).astype(np.float32)
    # positions differ per axis
    p3 = np.stack([np.zeros((1, 4)), np.arange(4)[None],
                   2 * np.arange(4)[None]]).astype(np.int32)
    out = apply_mrope(jnp.asarray(x), jnp.asarray(p3), 10000.0, (2, 3, 3))
    assert out.shape == x.shape
    # t-axis positions all zero -> first section unrotated
    np.testing.assert_allclose(np.asarray(out)[..., :2], x[..., :2],
                               atol=1e-5)
    assert not np.allclose(np.asarray(out)[..., 2:8], x[..., 2:8])


def test_param_counts_match_published():
    expected = {"gemma-2b": (2.0, 3.0), "qwen2-1.5b": (1.2, 1.9),
                "yi-34b": (32, 36), "qwen2-72b": (70, 76),
                "rwkv6-7b": (6.5, 8.5), "recurrentgemma-9b": (7.5, 10.5),
                "qwen2-moe-a2.7b": (13, 15.5),
                "granite-moe-1b-a400m": (1.0, 1.7),
                "qwen2-vl-2b": (1.2, 1.9),
                "seamless-m4t-medium": (0.7, 1.6)}
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count() / 1e9
        assert lo <= n <= hi, (arch, n)
    # MoE active params
    assert 2.2 <= get_config("qwen2-moe-a2.7b").active_param_count() / 1e9 <= 3.2
    assert 0.3 <= get_config("granite-moe-1b-a400m").active_param_count() / 1e9 <= 0.6
