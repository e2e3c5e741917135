"""Device idle time inside the program's host spans: exact on a trace
built by hand, and the three readers on 0.2 s of a trace recorded on a
TPU v5e while it served the chat cell with the program's spans in it
(trimmed to that slice, events that cross its edges clipped to them,
moved to start at 0; nothing else changed)."""
import pathlib
import shutil

import pytest

from bench import cell, idle, trace
from bench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace"
SPANS = DATA / "qwen2-chat-spans.xplane.pb"
NO_SPANS = DATA / "qwen2-chat.xplane.pb"
E = trace.Event


def _hand_built():
    #     0   10   20   30   40   50   60   70   80   90  100
    # A:      [op)      [op)           [op)
    # B:  [op, the whole window                               )
    return trace.Trace(
        devices={"/device:TPU:0": [E("a", 10, 20), E("b", 30, 40),
                                   E("while", 60, 70), E("c", 62, 68)],
                 "/device:TPU:1": [E("d", 0, 100)]},
        host=[E("sched.fetch", 15, 35),      # two gap edges inside it
              E("sched.fetch", 50, 65),      # ends inside an op
              E("sched.fetch", 55, 80),      # overlaps the one before
              E("sched.fetch", -5, 5),       # starts before the window
              E("sched.select", 0, 100),     # another name
              E("sched.fetchx", 90, 100)])   # not matched by the anchor


def test_idle_inside_splits_gaps_at_span_edges():
    tr = _hand_built()
    pat = r"^sched\.fetch$"
    # plane A idle [0,10) [20,30) [40,60) [70,100); spans, merged and
    # clipped: [0,5) [15,35) [50,80) -> 5 + 10 + 10 + 10; plane B: 0
    assert idle.idle_inside(tr, (0.0, 100.0), pat) == (5 + 10 + 10 + 10) / 2
    # window [25, 75): idle [25,30) [40,60) [70,75) inside [25,35) [50,75)
    assert idle.idle_inside(tr, (25.0, 75.0), pat) == (5 + 10 + 5) / 2
    assert idle.idle_inside(tr, (0.0, 100.0), r"^sched\.select$") == \
        (10 + 10 + 20 + 30) / 2
    assert idle.idle_inside(tr, (0.0, 100.0), r"^rpc\.") == 0.0


def _ctx(window_ns, counters):
    return cell.Context(cell=None, family=None, dims=None, peak=None,
                        reduced=None,
                        steps=[], counters=counters,
                        window_s=window_ns / 1e9)


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """Puts a recorded trace where a traced run leaves its own."""
    monkeypatch.setattr(cell, "TRACE_DIR", tmp_path)

    def put(path):
        shutil.copy(path, tmp_path / "run.xplane.pb")
        return trace.load(str(path))
    return put


def _read(name, ctx):
    return tiny.load().reader(name)(ctx)


def test_the_new_readers_on_a_recorded_chip_trace(traced_run):
    tr = traced_run(SPANS)
    ops = tr.devices["/device:TPU:0"]
    window = (0.0, max(e.end for e in ops))
    worker = [h for h in tr.host
              if h.name in ("sched.admit", "sched.assemble",
                            "sched.dispatch", "sched.select")]
    counters = {"step_calls": float(sum(h.name == "sched.dispatch"
                                        for h in tr.host)),
                "step_host_ns": float(sum(h.dur for h in worker))}
    ctx = _ctx(window[1], counters)
    fetch = _read("idle_fetch_share", ctx)
    host = _read("idle_host_share", ctx)
    per_step = _read("host_ms_per_step", ctx)
    red = trace.reduce(tr, window, {})
    idle_share = 100.0 * (1.0 - red.busy_ns / red.window_ns)
    assert 0 < fetch and 0 < host
    assert fetch + host <= idle_share
    assert fetch == pytest.approx(100.0 * idle.idle_inside(
        tr, window, r"^sched\.fetch$") / window[1])
    assert 0 < per_step < 45.0


def test_a_program_without_spans_reads_nothing(traced_run):
    tr = traced_run(NO_SPANS)
    end = max(e.end for e in tr.devices["/device:TPU:0"])
    ctx = _ctx(end, {"decode_steps": 10.0, "batched_rows": 40.0})
    for name in ("idle_fetch_share", "idle_host_share", "host_ms_per_step"):
        assert _read(name, ctx) is None
