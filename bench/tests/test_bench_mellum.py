"""The Mellum2 family (``bench/families/mellum.py``): its seeded weights,
its reference against the served tiny cell, and the work it counts for
``attn_roofline``, ``step_mfu`` and ``moe_roofline``."""
import json
import time
import types

import jax
import numpy as np

from bench import cell, flops, spec, weights
from bench.tests import tiny

# the tiny Mellum2 cell, defined only under this directory
BENCH = tiny.DATA / "benchmark-mellum.json"
CELL = "tiny-mellum.chat"


def load():
    return spec.load_cell(CELL, benchmark=BENCH,
                          traffic_dirs=[tiny.DATA / "traffic"],
                          metric_dirs=spec.METRIC_DIRS,
                          family_dirs=spec.FAMILY_DIRS)


def _family():
    c = load()
    return c, c.family, c.family.dims(c.config)


def test_the_cell_config_reads_as_published():
    """The benchmark's configuration file: every published size, the
    period of three window layers and one full one, 16 of 64 experts."""
    c = spec.load_cell("mellum2-e16.code")
    m = c.family.dims(c.config)
    assert m.period == ("window", "window", "window", "full")
    assert (m.layers, m.d, m.heads, m.kv_heads, m.head_dim, m.vocab) == \
        (28, 2304, 32, 4, 128, 98304)
    assert (m.window, m.experts, m.top_k, m.held_first, m.held,
            m.d_expert) == (1024, 64, 8, 0, 16, 896)
    assert m.yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    cfg = c.family.program_config(c.config)
    assert cfg.layer_pattern == m.period and cfg.moe.num_held == 16
    assert cfg.rope_scaling.factor == 16.0


def test_seeded_weights_are_deterministic():
    _, fam, m = _family()

    def leaves(seed):
        return [np.asarray(a) for a in jax.tree_util.tree_leaves(
            fam.make(weights.key_of(seed), m))]
    a, b, other = leaves(5), leaves(5), leaves(6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, other))
    p = fam.make(weights.key_of(5), m)
    assert p["layers"]["moe"]["w_gate"].shape == (4, 2, 64, 32)  # 2 held
    assert p["layers"]["moe"]["router"].shape == (4, 64, 8)      # of 8
    assert p["layers"]["moe"]["router"].dtype == np.float32


def test_the_reference_agrees_with_the_served_tiny_cell():
    """A CPU run of the tiny cell through ``load_cell``: served in float32,
    every compared token is the reference's best."""
    out = cell.run(load(), 20261019, 2.0, False, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    gap = out["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"] == 0.01


def test_attention_work_counts_a_window_layer_by_its_window():
    _, fam, m = _family()                 # window 8, 3 window + 1 full
    b = flops.dtype_bytes(m)
    pos = np.arange(10, 14)               # a 4-token chunk at 10..13
    (nw, fw, bw), (nf, ff, bf) = fam.attention_work(m, [pos])
    assert (nw, nf) == (3, 1)
    per_key = 4.0 * m.heads * m.head_dim
    assert fw == per_key * 4 * 8          # min(p + 1, 8) = 8 keys each
    assert ff == per_key * (11 + 12 + 13 + 14)
    io = 2.0 * 4 * m.heads * m.head_dim * b
    # keys 3..13 (first query's window through the last position), 0..13
    assert bw == 2.0 * m.kv_heads * m.head_dim * 11 * b + io
    assert bf == 2.0 * m.kv_heads * m.head_dim * 14 * b + io
    (_, fw, _), _ = fam.attention_work(m, [np.array([2])])
    assert fw == per_key * 3              # min(3, 8)


def test_expert_work_matches_a_hand_count():
    c = spec.load_cell("mellum2-e16.code")
    m = c.family.dims(c.config)
    layers, f, b = c.family.expert_work(m, [np.arange(32), np.array([700])])
    # 33 tokens x (8 x 16 / 64 = 2 held experts) x 2 x 3 x 2,304 x 896
    assert layers == 28
    assert f == 33 * 2 * 2 * 3 * 2304 * 896
    # 16 experts' weights once, and each pair's input and output row
    assert b == (16 * 3 * 2304 * 896 + 2 * 66 * 2304) * 2
    assert c.family.expert_work(m, [])[1] == 0.0


def test_token_flops_count_routed_and_windowed_work():
    c = spec.load_cell("mellum2-e16.code")
    m = c.family.dims(c.config)
    per_layer = 2 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
                     + 2 * 3 * 2304 * 896)
    keys = 7 * 3000 + 21 * 1024
    assert c.family.token_flops(m, 3000) == \
        28 * per_layer + 4 * 32 * 128 * keys


def test_moe_roofline_reads_the_expert_ops_and_nothing_else():
    c, fam, m = _family()
    read = c.reader("moe_roofline")
    live = np.array([1, 0])
    pos = np.array([[10, 11, 12, 13], [0, 0, 0, 0]])
    last = np.array([3, 0])
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}

    def ctx(ns):
        red = types.SimpleNamespace(kernel_ns={"experts": ns})
        return types.SimpleNamespace(
            family=fam, dims=m, peak=peak, steps=[("mixed", live, pos, last)],
            reduced=lambda patterns: red)
    assert read(ctx(0.0)) is None          # a program without the op
    layers, f, b = fam.expert_work(m, [pos[0]])
    want = layers * flops.roofline_seconds(f, b, peak)
    assert np.isclose(read(ctx(1e6)), 100.0 * want / 1e-3)
    dense = types.SimpleNamespace(family=object(), steps=[1])
    assert read(dense) is None             # a family without experts


def test_stats_export_the_peak_blocks_of_each_pool():
    """``Stats`` over the RPC carries ``kv_full_blocks`` and
    ``kv_window_blocks``, the peak live blocks of the full layers' pool
    and of the window layers' rings."""
    served = cell.Served(load(), 5)
    try:
        served.warm()
        prompt = np.arange(40, dtype=np.int32)[None] % 512
        served.impl.batcher.submit(prompt, max_new_tokens=4).result(
            timeout=300)
        from repro.core.rpc import Channel, TcpTransport
        from repro.serving.service import InferenceService
        ch = Channel(TcpTransport.connect(served.host, served.port))
        try:
            got = ch.typed(InferenceService).Stats({})
        finally:
            ch.close()
        values = dict(zip(got["names"].split("\n"),
                          np.asarray(got["values"])))
        stats = served.impl.batcher.stats
        lo = served.impl.batcher.cache.layout
        assert values["kv_full_blocks"] == stats["kv_full_blocks"] >= 11
        assert 0 < values["kv_window_blocks"] == stats["kv_window_blocks"] \
            <= lo.rows * lo.ring_blocks
    finally:
        served.close()


def test_the_benchmark_entry_names_the_metric_and_the_cell():
    bench = json.loads(spec.BENCHMARK.read_text())
    metric = {x["name"]: x for x in bench["per_layer"]}["moe_roofline"]
    assert metric["workloads"] == ["mellum2-e16.code"]
    assert metric["moves"] == "lat_p50_ms" and metric["layer"] == "kernels"
