"""Model code per configuration: the configuration file's ``family`` names
``<family dir>/<family>.py``, and the harness reaches model code only
through that lookup.  The family of the tiny cell, the one both served
configurations name, gives what the harness gave before the lookup
existed: the expected values below were recorded from that harness on a
CPU."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import cell, spec, weights
from bench.tests import tiny

SEED = 20261018
TOKENS = (np.arange(37) * 13 + 5) % 512
ROWS = [0, 17, 36]
WEIGHTS_SHA256 = \
    "554a00996b313835227b3c435a67b636d197324f2767e41a70d5eee58e6ffa7b"
LOGITS = [  # the reference's first four logits at ROWS
    [-0.009691670536994934, 0.008610528893768787, 0.006911018397659063,
     0.006558548659086227],
    [-0.006776383612304926, -0.007022760342806578, 0.007572988048195839,
     0.013474073261022568],
    [0.019143464043736458, -0.03899240866303444, 0.07476716488599777,
     0.01741688698530197]]
LOGITS_SUM = 0.282509116213987
FP8_LOGITS = [
    [-0.013000407256186008, 0.007096415851265192, 0.009207390248775482,
     0.0033434180077165365],
    [0.0008533938089385629, -0.009298854507505894, 0.007179712411016226,
     0.013505726121366024],
    [0.03131731227040291, -0.04403264820575714, 0.07124482840299606,
     0.01812088117003441]]
FP8_SUM = 0.2196432202035794


def _family():
    c = tiny.load()
    return c.family, c.family.dims(c.config)


def test_the_configuration_key_picks_the_file():
    c = tiny.load()
    assert c.family.__file__ == str(
        (spec.FAMILY_DIRS[0] / f"{c.config['family']}.py").resolve())
    assert tiny.load().family is c.family       # loaded once per file


def test_family_weights_are_the_parents_bit_for_bit():
    fam, m = _family()
    p = fam.make(weights.key_of(SEED), m)
    h = hashlib.sha256()
    for path, a in sorted(jax.tree_util.tree_leaves_with_path(p),
                          key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


@pytest.mark.parametrize("quant,first,total", [
    (None, LOGITS, LOGITS_SUM), ("fp8", FP8_LOGITS, FP8_SUM)])
def test_family_reference_logits_are_the_parents(quant, first, total):
    fam, m = _family()
    p = fam.make(weights.key_of(SEED), m)
    got = np.asarray(fam.logits(p, m, TOKENS, ROWS, quant=quant))
    assert got.shape == (len(ROWS), m.vocab)
    np.testing.assert_allclose(got[:, :4], first, rtol=1e-6, atol=1e-9)
    assert float(got.astype(np.float64).sum()) == pytest.approx(
        total, rel=1e-5)


def test_family_counts_are_the_parents():
    fam, m = _family()
    assert fam.token_flops(m, np.array([1, 7, 100])).tolist() == [
        147968.0, 151040.0, 198656.0]
    assert fam.head_flops(m) == 65536.0
    assert fam.attention_work(
        m, [np.array([4, 5, 6]), np.array([0]), np.array([99])]) == [
            (m.layers, 30464.0, 15104.0)]


@pytest.mark.parametrize("family,error", [
    (None, KeyError),                 # the key left out
    ("no_such_family", FileNotFoundError),
    ("../families/x", ValueError),    # not a name
])
def test_a_missing_or_unknown_family_fails(tmp_path, family, error):
    config = tiny.load().config
    config = {k: v for k, v in config.items() if k != "family"}
    if family is not None:
        config["family"] = family
    (tmp_path / "c.json").write_text(json.dumps(config))
    bench = json.loads((tiny.DATA / "benchmark.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], file="c.json")]
    bench["workloads"] = [bench["workloads"][0]]
    (tmp_path / "benchmark.json").write_text(json.dumps(bench))
    with pytest.raises(error):
        spec.load_cell(tiny.NAME, benchmark=tmp_path / "benchmark.json",
                       traffic_dirs=[tiny.DATA / "traffic"],
                       family_dirs=[tiny.DATA / "families",
                                    *spec.FAMILY_DIRS])


class _Reduced:
    kernel_ns = {"attn": 2e9}
    window_ns = 1e9
    busy_ns = 0.5e9


def test_a_family_defined_only_under_a_test_directory():
    c = tiny.load(tiny.COUNTED)
    calls = c.family.calls
    calls.clear()
    out = tiny.run(20261019, name=tiny.COUNTED)
    assert out["correct"], out["checks"]
    # the served weights and the program's config, then the reference's
    # weights and its logits, all through the test family
    assert calls["dims"] >= 1 and calls["program_config"] == 1
    assert calls["make"] == 2 and calls["logits"] >= 1
    # both FLOP readers count through the cell's family
    m = c.family.dims(c.config)
    pos = np.array([[0, 1, 2, 2], [9, 9, 9, 9]])
    ctx = cell.Context(
        cell=c, family=c.family, dims=m,
        peak={"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
        reduced=lambda patterns=None: _Reduced,
        steps=[("mixed", np.array([5, 7]), pos, np.array([2, 0]))],
        counters={"batched_rows": 1.0}, window_s=1.0)
    for name in ("step_mfu", "attn_roofline"):
        assert c.reader(name)(ctx) > 0
    assert calls["token_flops"] == 2 and calls["head_flops"] == 1
    assert calls["attention_work"] == 1
