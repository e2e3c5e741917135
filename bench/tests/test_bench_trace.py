"""The trace reduction, on half a second of a trace recorded on a TPU v5e
while it served the chat cell (trimmed to that slice; nothing else
changed)."""
import pathlib
import re

import numpy as np
import pytest

from bench import trace

PATH = pathlib.Path(__file__).resolve().parent / "data" / "trace" / \
    "qwen2-chat.xplane.pb"
ATTN = re.compile(r"^paged_(prefill_)?attention$")


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(PATH))


def _window(tr):
    evs = tr.devices["/device:TPU:0"]
    return evs[0].start, max(e.end for e in evs)


def test_device_ops_and_host_events_are_read(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    evs = tr.devices["/device:TPU:0"]
    assert len(evs) == 4137
    assert [e.op for e in evs].count("while") == 1     # one layer loop
    assert any(h.name == "np.asarray(jax.Array)" for h in tr.host)


def test_busy_is_the_union_of_op_intervals(tr):
    lo, hi = _window(tr)
    red = trace.reduce(tr, (lo, hi), {})
    # a 1 ns grid over the window, marked by every op: the union by brute
    # force, in microseconds to keep it small
    us = np.zeros(int((hi - lo) / 1e3) + 2, bool)
    for e in tr.devices["/device:TPU:0"]:
        us[int((e.start - lo) / 1e3):int(np.ceil((e.end - lo) / 1e3))] = True
    assert red.busy_ns == pytest.approx(us.sum() * 1e3, rel=0.01)
    assert red.window_ns == hi - lo
    assert 0 < red.busy_ns <= red.window_ns
    idle = sum(t for _, t in red.idle)
    assert idle == pytest.approx(red.window_ns - red.busy_ns, rel=1e-9)


def test_kernel_time_and_op_totals_count_leaf_ops_once(tr):
    lo, hi = _window(tr)
    red = trace.reduce(tr, (lo, hi), {"attn": ATTN})
    evs = tr.devices["/device:TPU:0"]
    want = sum(e.dur for e in evs
               if e.name.startswith("%paged_prefill_attention."))
    assert red.kernel_ns["attn"] == pytest.approx(want)
    ops = dict(red.ops)
    assert "while" not in ops                 # the layer loop holds the rest
    assert ops["paged_prefill_attention"] == pytest.approx(want)
    leaf = sum(e.dur for e in trace.leaves(evs))
    assert sum(ops.values()) == pytest.approx(leaf)
    assert sum(ops.values()) <= red.busy_ns * 1.0001


def test_breakdown_shape(tr):
    red = trace.reduce(tr, _window(tr), {})
    b = red.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "paged_prefill_attention"
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert all(isinstance(t, float) for _, t in b["device_ops"])


def test_op_names_lose_their_instance_number():
    ev = trace.Event("%copy_bitcast_fusion.7 = bf16[12000,2] fusion(x)",
                     0, 1)
    assert ev.op == "copy_bitcast_fusion"
    assert trace.Event("np.asarray(jax.Array)", 0, 1).op == \
        "np.asarray(jax.Array)"
