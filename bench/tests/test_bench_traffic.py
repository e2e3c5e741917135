"""The traffic generator: tokens reproducible from the seed, every prompt width
in the declared set, and every run given the same sizes and arrivals."""

import numpy as np
import pytest

from bench import spec, traffic

# the benchmark's mixes and the tests' own (a closed-loop one among them)
DIRS = (*spec.TRAFFIC_DIRS, spec.BENCH / "tests" / "data" / "traffic")
MIXES = sorted(p.stem for d in DIRS for p in d.glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return spec._find(name, DIRS, "traffic mix")


@pytest.mark.parametrize("mix", MIXES)
def test_same_schedule_every_run_tokens_from_the_seed(mix):
    a = traffic.plan(_mix(mix), 20)
    assert a == traffic.plan(_mix(mix), 20)
    r = a.requests[0]
    np.testing.assert_array_equal(traffic.prompt_tokens(BIG_SEED, r, 1000),
                                  traffic.prompt_tokens(BIG_SEED, r, 1000))
    assert not np.array_equal(traffic.prompt_tokens(BIG_SEED, r, 1000),
                              traffic.prompt_tokens(BIG_SEED + 1, r, 1000))
    other = dict(_mix(mix), schedule_seed=_mix(mix)["schedule_seed"] + 1)
    assert traffic.plan(other, 20).requests != a.requests


@pytest.mark.parametrize("mix", MIXES)
def test_prompt_widths_are_declared(mix):
    spec_ = _mix(mix)
    widths = set(traffic.prompt_widths(spec_))
    for seed in (0, 7, BIG_SEED):
        p = traffic.plan(spec_, 45)
        assert {r.prompt_len for r in p.requests} <= widths
        assert all(spec_["output"]["min"] <= r.max_new
                   <= spec_["output"]["max"] for r in p.requests)
        toks = traffic.prompt_tokens(seed, p.requests[0], 151936)
        assert toks.shape == (1, p.requests[0].prompt_len)
    assert len(widths) <= 64          # the service's plan-width cap


@pytest.mark.parametrize("mix", MIXES)
def test_each_window_or_round_is_a_stratified_sample(mix):
    spec_ = _mix(mix)
    p = traffic.plan(spec_, 45)
    if spec_["loop"] == "open":
        group = [r for r in p.requests if p.measured(r, 0)]
    else:       # a round: the k-th request of every client
        group = p.requests[:spec_["clients"]]
    n = len(group)
    q = (np.arange(n) + 0.5) / n
    for key, dist in (("prompt_len", "prompt"), ("max_new", "output")):
        got = sorted(getattr(r, key) for r in group)
        assert got == sorted(traffic._lengths(spec_[dist], q))
        assert [getattr(r, key) for r in group] != got     # shuffled


def test_open_loop_arrivals_fill_the_window_exactly():
    spec_ = _mix("chat")
    p = traffic.plan(spec_, 45)
    due = np.array([r.due for r in p.requests])
    assert due[0] == 0 and np.all(np.diff(due) > 0)
    assert p.window == (spec_["ramp_s"], spec_["ramp_s"] + 45)
    inside = [r for r in p.requests if p.measured(r, 0)]
    assert len(inside) == round(spec_["rate_per_s"] * 45)
    assert inside[0].due == spec_["ramp_s"]
