"""The swap/preemption tier: KV swap-to-host, SLO-aware scheduling.

Three layers of invariants:

  * ``PagedKVCache`` swap primitives — a swap round-trip is
    content-identical (into whatever physical blocks are free at resume
    time), refcount-aware (a block shared with another request or the
    prefix index is never yanked out from under it), an absent block
    never satisfies a prefix match, and no resources leak in either
    direction (shed-while-swapped reclaims the host image too).
  * ``PagedBatcher`` scheduling — preempt/resume is token-identical to
    an uncontended run, victims are chosen lowest-priority-first /
    most-blocks-first and swapped whole, and a paged-out request whose
    deadline expires is shed with everything reclaimed.
  * The SLO controller — halves/doubles ``max_step_tokens`` toward the
    more-violated of TTFT/TPOT, clamped, window-reset after each move.

Plus the stats-presence regression: every counter key exists from
construction, so dashboards and tests can rely on presence rather than
first increment.
"""
import threading
import time

import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.serving import (CacheOOM, ContinuousBatcher, Engine,
                           PagedBatcher, PagedKVCache, ServeConfig,
                           ShedError)

# ---------------------------------------------------------------------------
# PagedKVCache swap primitives (no engine, tiny geometry)
# ---------------------------------------------------------------------------


def _cache(**kw):
    kw.setdefault("prefix_cache", True)
    return PagedKVCache(num_layers=2, num_kv_heads=1, head_dim=16,
                        cache_len=64, block_size=16, num_blocks=9,
                        max_concurrent=4, **kw)


def _fill_blocks(cache, blocks, seed):
    """Stamp random content into ``blocks``; returns {block: (k, v)}."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    k = np.array(cache.pool["k"])
    v = np.array(cache.pool["v"])
    content = {}
    for b in blocks:
        kb = rng.standard_normal(k[:, b].shape).astype(k.dtype)
        vb = rng.standard_normal(v[:, b].shape).astype(v.dtype)
        k[:, b] = kb
        v[:, b] = vb
        content[b] = (kb, vb)
    cache.pool = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    return content


def test_swap_roundtrip_restores_content_into_fresh_blocks():
    cache = _cache()
    cache.allocate("A", 40)                       # 3 blocks
    old = list(cache.allocator.blocks_of("A"))
    content = _fill_blocks(cache, old, seed=1)

    n = cache.swap_out("A")
    assert n == 3 and cache.is_swapped("A")
    assert cache.swapped_blocks("A") == 3
    assert cache.allocator.num_free == cache.allocator.capacity

    # claim the freed physical blocks and clobber their contents — the
    # host image, not the pool, must be what swap_in restores from
    cache.allocate("B", 64)                       # 4 blocks, LIFO overlap
    _fill_blocks(cache, cache.allocator.blocks_of("B"), seed=2)

    row = cache.swap_in("A")
    assert not cache.is_swapped("A")
    new = list(cache.allocator.blocks_of("A"))
    assert list(row[:3]) == new
    k = np.array(cache.pool["k"])
    v = np.array(cache.pool["v"])
    for o, fresh in zip(old, new):
        np.testing.assert_array_equal(k[:, fresh], content[o][0])
        np.testing.assert_array_equal(v[:, fresh], content[o][1])


def test_swap_out_never_frees_blocks_shared_with_others():
    cache = _cache()
    cache.allocate("A", 40)
    shared = cache.allocator.blocks_of("A")[0]
    cache.allocator.share(shared, "B")
    cache.swap_out("A")
    # A's exclusive blocks went back to the free list; the shared one
    # lost only A's reference and stays resident for B
    assert not cache.allocator.is_free(shared)
    assert cache.allocator.blocks_of("B") == [shared]
    assert cache.allocator.refcount(shared) == 1


def test_prefix_sharer_survives_victim_swap_out():
    cache = _cache()
    toks = np.arange(40, dtype=np.int32)          # 2 full blocks + tail
    cache.allocate_prefix("A", 40, toks)
    cache.register_progress("A", toks, 40)
    _, matched, shared = cache.allocate_prefix("B", 40, toks)
    assert shared == 2
    b_blocks = list(cache.allocator.blocks_of("B"))

    cache.swap_out("A")
    # B still reads the shared prefix blocks; its table is untouched
    assert cache.allocator.blocks_of("B") == b_blocks
    assert all(not cache.allocator.is_free(b) for b in b_blocks)
    cache.swap_in("A")
    cache.release("A")
    cache.release("B")


def test_absent_blocks_never_satisfy_prefix_matches():
    cache = _cache()
    toks = np.arange(40, dtype=np.int32)
    cache.allocate_prefix("A", 40, toks)
    cache.register_progress("A", toks, 40)
    stamped = _fill_blocks(cache, list(cache.allocator.blocks_of("A")),
                           seed=3)
    assert cache.match_prefix(toks) == 2

    cache.swap_out("A")
    # the index holds its own reference, so the registered blocks are
    # STILL RESIDENT (content intact) — a match here is safe by design
    assert cache.match_prefix(toks) == 2

    # force real absence: allocations evict the now-idle indexed blocks
    cache.allocate("B", 64)
    cache.allocate("C", 64)                       # 8 > 6 free -> evicts 2
    assert cache.match_prefix(toks) == 0, \
        "evicted (absent) blocks must never satisfy a prefix match"

    # and the victim still round-trips: swap_out imaged the content, so
    # the index dropping the blocks afterwards loses nothing
    cache.release("B")
    cache.release("C")
    cache.swap_in("A")
    k = np.array(cache.pool["k"])
    for o, fresh in zip(stamped, cache.allocator.blocks_of("A")):
        np.testing.assert_array_equal(k[:, fresh], stamped[o][0])


def test_release_while_swapped_reclaims_host_and_device():
    cache = _cache()
    cache.allocate("A", 40)
    cache.swap_out("A")
    assert cache.is_swapped("A")
    cache.release("A")
    assert not cache.is_swapped("A")
    assert cache.allocator.num_free == cache.allocator.capacity


def test_swap_in_oom_is_all_or_nothing():
    cache = _cache()
    cache.allocate("A", 40)
    cache.swap_out("A")
    cache.allocate("B", 64)
    cache.allocate("C", 64)                       # pool exhausted
    free_before = cache.allocator.num_free
    with pytest.raises(CacheOOM):
        cache.swap_in("A")
    assert cache.is_swapped("A")                  # image intact
    assert cache.allocator.num_free == free_before
    cache.release("B")
    cache.swap_in("A")                            # now it fits
    assert not cache.is_swapped("A")


def test_double_swap_out_and_swap_in_without_image_are_errors():
    cache = _cache()
    cache.allocate("A", 40)
    cache.swap_out("A")
    with pytest.raises(ValueError):
        cache.swap_out("A")
    with pytest.raises(ValueError):
        cache.swap_in("B")


# ---------------------------------------------------------------------------
# PagedBatcher scheduling (real engine, reduced config)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("qwen2-1.5b"))


@pytest.fixture(scope="module")
def ref(cfg):
    """Uncontended reference: auto-sized pool, nothing ever preempts."""
    eng = Engine(cfg, ServeConfig(cache_len=64, max_new_tokens=48,
                                  max_batch=4, prefill_chunk=16,
                                  spec_decode=False, prefix_cache=False))
    batcher = PagedBatcher(eng, max_batch=4)
    yield eng, batcher
    batcher.close()


def _prompt(cfg, t, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, t)).astype(np.int32)


def _contended(cfg, ref_eng, num_blocks):
    """Small-pool engine sharing the reference params (token identity)."""
    eng = Engine(cfg, ServeConfig(cache_len=64, max_new_tokens=48,
                                  max_batch=4, prefill_chunk=16,
                                  num_blocks=num_blocks, spec_decode=False,
                                  prefix_cache=False),
                 params=ref_eng.params)
    return PagedBatcher(eng, max_batch=4)


def _wait(pred, timeout=120.0, what="condition"):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.001)


def test_preempt_resume_token_identical_to_uncontended(cfg, ref):
    ref_eng, ref_b = ref
    victim_p, high_p = _prompt(cfg, 16, 3), _prompt(cfg, 16, 4)
    want_v = ref_b.submit(victim_p, max_new_tokens=24).result(timeout=120)
    want_h = ref_b.submit(high_p, max_new_tokens=8).result(timeout=120)

    # 4 usable blocks: the victim (3) leaves too little for the high (2)
    b = _contended(cfg, ref_eng, num_blocks=5)
    try:
        emitted = threading.Event()
        fv = b.submit(victim_p, max_new_tokens=24, priority=0,
                      on_token=lambda i, t: emitted.set())
        assert emitted.wait(120), "victim never started decoding"
        fh = b.submit(high_p, max_new_tokens=8, priority=1)
        got_h = fh.result(timeout=120)
        got_v = fv.result(timeout=120)
        assert b.stats["preemptions"] >= 1
        assert b.stats["swap_ins"] >= 1
        assert b.stats["swapped_blocks"] >= 3
        np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(got_h, want_h)
    finally:
        b.close()


class _ManualDeadline:
    """A deadline the test flips, so no timing races decide the outcome."""

    def __init__(self):
        self.flag = False

    def expired(self):
        return self.flag


def test_swapped_victim_past_deadline_is_shed_with_reclaim(cfg, ref):
    ref_eng, _ = ref
    victim_p, high_p = _prompt(cfg, 16, 5), _prompt(cfg, 16, 6)
    b = _contended(cfg, ref_eng, num_blocks=5)
    try:
        dl = _ManualDeadline()
        emitted = threading.Event()
        fv = b.submit(victim_p, max_new_tokens=24, priority=0, deadline=dl,
                      on_token=lambda i, t: emitted.set())
        assert emitted.wait(120), "victim never started decoding"
        # a LONG high keeps the pool full, so the victim stays paged out
        fh = b.submit(high_p, max_new_tokens=24, priority=1)
        _wait(lambda: b.stats["preemptions"] >= 1, what="preemption")
        dl.flag = True
        with pytest.raises(ShedError, match="swapped out"):
            fv.result(timeout=120)
        fh.result(timeout=120)
        # shed while paged out reclaimed BOTH tiers: no host image left,
        # every device block back on the free list
        _wait(lambda: b.cache.num_free_blocks == b.cache.allocator.capacity,
              what="block reclaim")
        assert not b.cache._swapped
        assert not b._preempted
    finally:
        b.close()


def test_victim_selection_lowest_priority_most_blocks_first(cfg, ref):
    ref_eng, ref_b = ref
    big_p, small_p, high_p = (_prompt(cfg, 16, s) for s in (7, 8, 9))
    want_big = ref_b.submit(big_p, max_new_tokens=48).result(timeout=120)
    want_small = ref_b.submit(small_p, max_new_tokens=16).result(timeout=120)
    want_high = ref_b.submit(high_p, max_new_tokens=32).result(timeout=120)

    # 8 usable blocks: big holds 4, small holds 2, the high needs 3 > 2
    b = _contended(cfg, ref_eng, num_blocks=9)
    try:
        victims = []
        orig = b._preempt
        b._preempt = lambda req: (victims.append(req), orig(req))[1]
        counts = {"big": 0, "small": 0}

        def hook(name):
            def on_token(i, t):
                counts[name] += 1
            return on_token

        f_big = b.submit(big_p, max_new_tokens=48, priority=0,
                         on_token=hook("big"))
        f_small = b.submit(small_p, max_new_tokens=16, priority=0,
                           on_token=hook("small"))
        _wait(lambda: counts["big"] >= 1 and counts["small"] >= 1,
              what="both lows decoding")
        f_high = b.submit(high_p, max_new_tokens=32, priority=1)
        got_high = f_high.result(timeout=120)
        got_small = f_small.result(timeout=120)
        got_big = f_big.result(timeout=120)

        # equal priority -> the request holding the MOST blocks is paged
        # out first (fewest victims for the most relief), and it alone
        # already covers the high's need
        assert victims, "admission never preempted"
        assert victims[0].future is f_big
        np.testing.assert_array_equal(got_big, want_big)
        np.testing.assert_array_equal(got_small, want_small)
        np.testing.assert_array_equal(got_high, want_high)
    finally:
        b.close()


# ---------------------------------------------------------------------------
# SLO controller (pure host-side state, no traffic needed)
# ---------------------------------------------------------------------------


def test_slo_controller_halves_budget_on_tpot_pressure(ref):
    eng, _ = ref
    b = PagedBatcher(eng, max_batch=4)
    try:
        b.max_step_tokens = 64
        b._tpot_obs.extend([(0.2, 0.1)] * 8)      # 100% violations
        b._slo_adjust()
        assert b.max_step_tokens == 32
        assert b.stats["slo_adjustments"] == 1
        assert not b._tpot_obs, "window must reset after a move"
        for _ in range(10):                        # clamp floor
            b._tpot_obs.extend([(0.2, 0.1)] * 8)
            b._slo_adjust()
        assert b.max_step_tokens == b.max_batch + 1
    finally:
        b.close()


def test_slo_controller_doubles_budget_on_ttft_pressure(ref):
    eng, _ = ref
    b = PagedBatcher(eng, max_batch=4)
    try:
        b.max_step_tokens = 16
        b._ttft_obs.extend([(0.5, 0.1)] * 8)
        b._slo_adjust()
        assert b.max_step_tokens == 32
        for _ in range(10):                        # clamp ceiling
            b._ttft_obs.extend([(0.5, 0.1)] * 8)
            b._slo_adjust()
        assert b.max_step_tokens == b._step_budget_cap
    finally:
        b.close()


def test_slo_controller_holds_below_violation_threshold(ref):
    eng, _ = ref
    b = PagedBatcher(eng, max_batch=4)
    try:
        b.max_step_tokens = 64
        b._tpot_obs.extend([(0.2, 0.1)] + [(0.05, 0.1)] * 7)   # 12.5%
        b._slo_adjust()
        assert b.max_step_tokens == 64
        assert b.stats["slo_adjustments"] == 0
        assert len(b._tpot_obs) == 8, "no move -> window keeps filling"
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Stats presence regression
# ---------------------------------------------------------------------------

REQUIRED_KEYS = {"requests", "rows", "shed", "decode_steps", "batched_rows",
                 "prefill_chunks", "mixed_steps", "admitted_in_flight",
                 "dense_fallbacks", "worker_errors", "prefix_hits",
                 "prefix_tokens_reused", "cow_copies", "spec_steps",
                 "spec_proposed", "spec_accepted", "preemptions",
                 "swapped_blocks", "swap_ins", "slo_violations",
                 "slo_adjustments", "step_calls", "step_host_ns",
                 "kv_full_blocks", "kv_window_blocks"}


def test_paged_stats_keys_present_from_construction(ref):
    eng, _ = ref
    b = PagedBatcher(eng, max_batch=4)
    try:
        assert REQUIRED_KEYS <= set(b.stats)
        assert all(v == 0 for v in b.stats.values()), \
            "counters must start at zero, not appear on first increment"
        snap = b.collect_stats()
        assert set(b.stats) <= set(snap)
        for gauge in ("active_requests", "queued_requests",
                      "preempted_requests", "free_blocks",
                      "max_step_tokens"):
            assert gauge in snap
    finally:
        b.close()


def test_dense_stats_snapshot_has_queue_gauge(ref):
    eng, _ = ref
    b = ContinuousBatcher(eng, max_batch=4, window_s=0.01)
    try:
        snap = b.collect_stats()
        assert set(b.stats) <= set(snap)
        assert "queued_requests" in snap
    finally:
        b.close()
