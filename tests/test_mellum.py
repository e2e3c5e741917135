"""Mellum2's block on the paged path, at a tiny size on the CPU: a period
of three window layers and one full (YaRN) layer, window 8, KV blocks of
4 tokens, 8 experts routed top-2.

The float32 reference is the benchmark's copy (``bench/families/
mellum.py``), which imports nothing of the program: the weights it makes
are handed to the program.  The tolerances are set from the float32
arithmetic both sides do; computing the program in fp8 (the family's
``quant="fp8"`` control, read here as the program would read it) misses
them, which ``test_fp8_control_misses_the_tolerance`` checks.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from repro.kernels import paged_attention as pa
from repro.kernels import ref as kref
from repro.models import get_model
from repro.models import moe as M
from repro.serving import Engine, PagedBatcher, ServeConfig

FAMILY = spec.load_family("mellum")

# Float32 on both sides, summed in different orders (kernels per block,
# reference per query block; grouped matmuls vs a dense expert loop):
# differences are a few float32 ulps of logits of order 0.1-1.  fp8
# operands move them by about 1e-2 (checked below).
LOGIT_ATOL = 2e-4
# one MoE layer's output (order 1): the same arithmetic, fewer steps
MOE_ATOL = 1e-5


def tiny_config(held_first=0, held=8, window=8):
    """A configuration file's keys at a tiny size (catalog key names)."""
    return {
        "name": "tiny-mellum", "family": "mellum",
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4,
        "vocab_size": 512, "rms_norm_eps": 1e-6,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "sliding_window": window,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                               "factor": 16,
                               "original_max_position_embeddings": 8192,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "attention_bias": False, "norm_topk_prob": True,
        "tie_word_embeddings": False, "moe_intermediate_size": 32,
        "num_experts_per_tok": 2, "num_experts": held,
        "experts_published": 8, "experts_held_first": held_first,
        "program": {"embedding_multiplier": 1.0, "dtype": "float32"},
    }


def _model(held_first=0, held=8, seed=7):
    conf = tiny_config(held_first, held)
    m = FAMILY.dims(conf)
    return FAMILY.program_config(conf), m, FAMILY.make(weights.key_of(seed),
                                                       m)


def _ring_steps(cfg, params, prompts, new, chunk, block, serve_spec=4):
    """Prefill ``prompts`` (one per batch row) in chunks through the
    paged step, then greedy-decode ``new`` tokens; returns, for each row,
    the logits of every step after its prompt ([new, vocab]) and the
    tokens fed."""
    from repro.serving import PagedKVCache
    from repro.serving.kv_cache import ring_geometry
    model = get_model(cfg)
    b = len(prompts)
    full, win = model.paged_layers()
    ring_bs, ring = ring_geometry(cfg.window, block,
                                  max(chunk, serve_spec + 1))
    total = max(len(p) for p in prompts) + new
    cache = PagedKVCache(num_layers=full, num_kv_heads=cfg.num_kv_heads,
                         head_dim=cfg.head_dim, cache_len=total,
                         block_size=block, max_concurrent=b,
                         dtype=cfg.dtype, prefix_cache=False,
                         ring_blocks=ring, ring_layers=win,
                         ring_block_size=ring_bs)
    pool = model.init_paged_pool(cache.layout.num_blocks, block,
                                 cache.ring_rows, ring_bs)
    tables = np.stack([cache.allocate(i, total) for i in range(b)])
    step = jax.jit(model.paged_step)
    seqs = [list(p) for p in prompts]
    done = [0] * b
    out = [[] for _ in range(b)]
    while min(len(o) for o in out) < new:
        toks = np.zeros((b, chunk), np.int32)
        pos = np.zeros((b, chunk), np.int32)
        last = np.zeros(b, np.int32)
        for i in range(b):
            adv = min(chunk, len(seqs[i]) - done[i])
            toks[i, :adv] = seqs[i][done[i]:done[i] + adv]
            pos[i] = done[i] + np.minimum(np.arange(chunk), adv - 1)
            last[i] = adv - 1
        logits, pool = step(params, jnp.asarray(toks), pool,
                            jnp.asarray(tables), jnp.asarray(pos),
                            jnp.asarray(last))
        logits = np.asarray(logits)
        for i in range(b):
            done[i] += int(last[i]) + 1
            if done[i] == len(seqs[i]) and len(out[i]) < new:
                out[i].append(logits[i])
                seqs[i].append(int(np.argmax(logits[i])))
    return [np.stack(o) for o in out], seqs, ring * ring_bs


def test_paged_chunks_then_decode_through_ring_wraps_match_reference():
    """Two rows of different lengths, prefilled 4 tokens a step, then
    decoded until each row's window layers have wrapped their ring more
    than three times; every step's logits against the reference's full
    forward over the same tokens."""
    cfg, m, params = _model(held_first=2, held=2)   # a share: experts 2-3
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, 22), rng.integers(0, 512, 9)]
    new = 76
    got, seqs, ring_positions = _ring_steps(cfg, params, prompts, new,
                                            chunk=4, block=4)
    for i, p in enumerate(prompts):
        assert len(seqs[i]) - 1 > 3 * ring_positions + cfg.window
        rows = np.arange(len(p) - 1, len(p) - 1 + new)
        want = np.asarray(FAMILY.logits(params, m, np.asarray(seqs[i]),
                                        rows))
        np.testing.assert_allclose(got[i], want, atol=LOGIT_ATOL, rtol=0)


def test_fp8_control_misses_the_tolerance():
    """The reference from fp8 operands, the lower precision the tolerance
    must refuse, lies outside LOGIT_ATOL of the float32 reference."""
    cfg, m, params = _model(held_first=2, held=2)
    toks = np.random.default_rng(3).integers(0, 512, 40)
    rows = np.arange(8, 40)
    f32 = np.asarray(FAMILY.logits(params, m, toks, rows))
    fp8 = np.asarray(FAMILY.logits(params, m, toks, rows, quant="fp8"))
    assert np.max(np.abs(f32 - fp8)) > 10 * LOGIT_ATOL


def _moe_params(cfg, seed=0):
    return M.init_moe(jax.random.PRNGKey(seed), cfg)


def _share(p, first, held):
    return dict(p, **{k: p[k][first:first + held]
                      for k in ("w_gate", "w_up", "w_down")})


STACK_DEPTH, STACK_LAYER = 3, 1


def _stacked(p, seed=9):
    """``p``'s experts as layer STACK_LAYER of a STACK_DEPTH-layer stack
    [depth * held, ...], the other layers' experts random: the form the
    paged step passes, so a test that reads the wrong layer fails."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    out = dict(p)
    for key, k in zip(keys, ("w_gate", "w_up", "w_down")):
        w = p[k]
        stack = jax.random.normal(key, (STACK_DEPTH,) + w.shape, w.dtype)
        out[k] = stack.at[STACK_LAYER].set(w).reshape((-1,) + w.shape[1:])
    return out


def _dropless(p, x, cfg, valid):
    return M.moe_ffn_dropless(_stacked(p), x, cfg, valid, STACK_LAYER)


def test_four_shares_sum_to_the_uncut_layer():
    cfg, _, _ = _model()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64), jnp.float32)
    valid = jnp.ones((3, 5), bool)
    whole = _dropless(p, x, cfg, valid)
    parts = 0.0
    for first in range(0, 8, 2):
        c = cfg.__class__(**{**cfg.__dict__, "moe": cfg.moe.__class__(
            **{**cfg.moe.__dict__, "held_first": first, "held": 2})})
        parts = parts + _dropless(_share(p, first, 2), x, c, valid)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=MOE_ATOL, rtol=0)


def test_padding_tokens_change_no_row():
    """A row's output is the same whatever padding (or other rows) the
    step carries beside it; padding tokens get no expert output."""
    cfg, _, _ = _model()
    p = _moe_params(cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (2, 6, 64), jnp.float32)
    valid = jnp.asarray([[True] * 6, [True, False, False, False, False,
                                     False]])
    out = np.asarray(_dropless(p, x, cfg, valid))
    noise = jax.random.normal(k2, x.shape, jnp.float32) * 100
    x2 = jnp.where(valid[..., None], x, noise)
    out2 = np.asarray(_dropless(p, x2, cfg, valid))
    np.testing.assert_array_equal(out[np.asarray(valid)],
                                  out2[np.asarray(valid)])
    assert not out[~np.asarray(valid)].any()
    solo = np.asarray(_dropless(p, x[1:, :1], cfg, valid[1:, :1]))
    np.testing.assert_allclose(out[1, :1], solo[0], atol=MOE_ATOL, rtol=0)


def test_no_token_is_dropped_when_all_route_to_one_expert():
    """Every token of a 32-token chunk routed to held expert 3 (a router
    column far above the rest): all reach it, none is dropped, as the
    capacity dispatch would (capacity 1.25 * 32 * 2 / 8 = 10)."""
    cfg, _, _ = _model()
    p = _moe_params(cfg)
    router = jnp.zeros((64, 8), jnp.float32).at[:, 3].set(1.0) \
        .at[:, 5].set(0.5)
    p = dict(p, router=router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 32, 64),
                                  jnp.float32)) + 1.0
    got = np.asarray(_dropless(p, x, cfg, jnp.ones((1, 32), bool)))
    xt = np.asarray(x[0], np.float64)
    probs = jax.nn.softmax(np.asarray(x[0] @ router, np.float64), -1)
    want = 0.0
    for e in (3, 5):
        w = np.asarray(probs[:, e] / (probs[:, 3] + probs[:, 5]))[:, None]
        g = xt @ np.asarray(p["w_gate"][e], np.float64)
        u = xt @ np.asarray(p["w_up"][e], np.float64)
        act = g / (1 + np.exp(-g)) * u
        want = want + w * (act @ np.asarray(p["w_down"][e], np.float64))
    np.testing.assert_allclose(got[0], want, atol=MOE_ATOL, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 5])
def test_windowed_kernels_match_masked_dense_attention(t):
    """The windowed paged kernels (interpret mode) and their reference,
    reading a rotated ring, against plain attention over the contiguous
    cache with HF's sliding mask (key > query - window)."""
    rng = np.random.default_rng(t)
    b, hq, hkv, d, bs, window, r = 2, 4, 2, 16, 4, 8, 4
    ctx = np.array([23, 11])                     # keys 0..ctx-1 written
    keys = rng.standard_normal((b, hkv, 24, d)).astype(np.float32)
    vals = rng.standard_normal((b, hkv, 24, d)).astype(np.float32)
    q = rng.standard_normal((b, hq, t, d)).astype(np.float32)
    qpos = ctx[:, None] - t + np.arange(t)[None, :]      # last t positions
    # ring pool: row i owns blocks 1 + i*r .. ; logical block j -> slot j % r
    pool_k = np.zeros((1, 1 + b * r, hkv, bs, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    for i in range(b):
        for p in range(ctx[i]):
            blk = 1 + i * r + (p // bs) % r
            pool_k[0, blk, :, p % bs] = keys[i, :, p]
            pool_v[0, blk, :, p % bs] = vals[i, :, p]
    start = np.maximum(qpos[:, 0] - window + 1, 0) // bs
    table = np.stack([1 + i * r + (start[i] + np.arange(r)) % r
                      for i in range(b)]).astype(np.int32)
    want = []
    for i in range(b):
        o = kref.attention(jnp.asarray(q[i:i + 1]),
                           jnp.asarray(keys[i:i + 1, :, :ctx[i]]),
                           jnp.asarray(vals[i:i + 1, :, :ctx[i]]),
                           causal=True, window=window,
                           q_offset=int(qpos[i, 0]))
        want.append(np.asarray(o)[0])
    want = np.stack(want)
    args = (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table))
    got_ref = kref.paged_attention(*args, jnp.asarray(qpos, jnp.int32),
                                   jnp.int32(0), jnp.asarray(start),
                                   window=window)
    if t == 1:
        got_k = pa.paged_attention(args[0][:, :, 0], *args[1:],
                                   jnp.asarray(ctx, jnp.int32), jnp.int32(0),
                                   jnp.asarray(start), window=window,
                                   interpret=True)[:, :, None]
    else:
        got_k = pa.paged_prefill_attention(
            *args, jnp.asarray(qpos, jnp.int32), jnp.int32(0),
            jnp.asarray(start), window=window, interpret=True)
    # float32 throughout: a few ulps of values of order 1
    np.testing.assert_allclose(np.asarray(got_ref), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_k), want, atol=1e-5)


def _engine(cfg, params, **kw):
    sc = dict(cache_len=96, block_size=4, prefill_chunk=4, max_batch=4,
              max_new_tokens=48, spec_decode=False)
    sc.update(kw)
    return Engine(cfg, ServeConfig(**sc), params=params)


def test_prefix_cache_hit_is_refused_for_window_layers():
    cfg, _, params = _model()
    b = PagedBatcher(_engine(cfg, params, prefix_cache=True))
    try:
        assert b.prefix_refused and "window" in b.prefix_refused
        prompt = np.random.default_rng(5).integers(0, 512, (1, 24)) \
            .astype(np.int32)
        first = b.submit(prompt, max_new_tokens=6).result(timeout=300)
        again = b.submit(prompt, max_new_tokens=6).result(timeout=300)
        assert b.stats["prefix_hits"] == 0
        assert b.stats["prefix_tokens_reused"] == 0
        np.testing.assert_array_equal(first, again)
    finally:
        b.close()


def test_preempted_row_takes_its_ring_to_the_row_it_resumes_in():
    """A victim paged out mid-decode resumes in another batch row (the
    high-priority request took its row) and still decodes exactly as an
    uncontended run: its window layers' ring went with it."""
    cfg, _, params = _model()
    rng = np.random.default_rng(6)
    victim = rng.integers(0, 512, (1, 24)).astype(np.int32)
    high = rng.integers(0, 512, (1, 16)).astype(np.int32)
    ref = PagedBatcher(_engine(cfg, params, prefix_cache=False))
    try:
        want_v = ref.submit(victim, max_new_tokens=24).result(timeout=300)
        want_h = ref.submit(high, max_new_tokens=8).result(timeout=300)
    finally:
        ref.close()
    # 12 usable blocks of 4 tokens: the victim holds 12, the high needs 6
    b = PagedBatcher(_engine(cfg, params, prefix_cache=False,
                             num_blocks=13))
    try:
        started = threading.Event()
        fv = b.submit(victim, max_new_tokens=24, priority=0,
                      on_token=lambda i, t: started.set())
        assert started.wait(300), "victim never started decoding"
        fh = b.submit(high, max_new_tokens=8, priority=1)
        got_h = fh.result(timeout=300)
        got_v = fv.result(timeout=300)
        assert b.stats["preemptions"] >= 1 and b.stats["swap_ins"] >= 1
        np.testing.assert_array_equal(got_h, want_h)
        np.testing.assert_array_equal(got_v, want_v)
        assert 0 < b.stats["kv_window_blocks"] <= 4 * b.cache.layout.ring_blocks
        assert b.stats["kv_full_blocks"] >= 12
    finally:
        b.close()


def test_forked_candidates_carry_the_prompts_ring():
    """n = 2 greedy candidates of a prompt longer than the window decode
    exactly as the single request: the second row got the ring."""
    cfg, _, params = _model()
    prompt = np.random.default_rng(8).integers(0, 512, (1, 21)) \
        .astype(np.int32)
    b = PagedBatcher(_engine(cfg, params, prefix_cache=False))
    try:
        one = b.submit(prompt, max_new_tokens=10).result(timeout=300)
        two = b.submit(prompt, max_new_tokens=10, n=2).result(timeout=300)
        assert two.shape[0] == 2 and b.stats["forks"] == 1
        np.testing.assert_array_equal(two[0], one[0])
        np.testing.assert_array_equal(two[1], one[0])
    finally:
        b.close()
