"""The program's host spans and step counters, read as the benchmark reads
them: a served process at the test cell's widths answers page-encoded
``Infer`` calls under a profiler session on a CPU, and ``trace.load``
reads the spans back from the trace it wrote."""
import concurrent.futures as cf
import re
import time

import numpy as np
import pytest

from bench import trace
from bench.tests import tiny

WORKER = ("sched.admit", "sched.dense", "sched.assemble", "sched.dispatch",
          "sched.fetch", "sched.select")
STEP = ["sched.assemble", "sched.dispatch", "sched.fetch", "sched.select"]
SPANS = {"rpc.Infer", "ingest.admit", "sched.submit", "sched.retire", *WORKER}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.core.rpc import Channel, connected_pair
    from repro.serving import Engine, ServeConfig, build_server
    from repro.serving.service import (InferenceImpl, InferenceService,
                                       encode_prompt_page)
    c = tiny.load()
    config = c.config
    engine = Engine(c.family.program_config(config),
                    ServeConfig(**config["serve"], max_new_tokens=6))
    impl = InferenceImpl(engine)
    b = impl.batcher
    calls = []

    def counted(fn):
        def call(*a):
            calls.append(1)
            return fn(*a)
        return call
    b._step_fn = counted(b._step_fn)
    if b._verify_fn is not None:
        b._verify_fn = counted(b._verify_fn)
    server = build_server(engine, impl=impl)
    ct, st = connected_pair()
    server.serve_transport(st, blocking=False)
    ch = Channel(ct)
    inf = ch.typed(InferenceService)
    rng = np.random.default_rng(13)

    def infer(rows, width):
        page = encode_prompt_page(rng.integers(0, 512, (rows, width)))
        return inf.Infer({"page": page, "max_new_tokens": 6})

    infer(1, 24)                      # compiles outside the trace
    infer(b.max_batch + 1, 8)         # more rows than slots: dense engine
    directory = tmp_path_factory.mktemp("trace")
    trace.start(str(directory))
    with cf.ThreadPoolExecutor(4) as pool:
        # three paged requests, in flight together, and one dense
        done = list(pool.map(lambda a: infer(*a), [
            (1, 24), (1, 40), (1, 9), (b.max_batch + 1, 8)]))
    path = trace.stop(str(directory))
    yield {"impl": impl, "inf": inf, "calls": calls, "path": path,
           "answers": done}
    ch.close()
    b.close()


def _spans(path):
    """(name, start, end, rid) of every ``sched.``, ``rpc.`` and
    ``ingest.`` host event, from the trace's own stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if re.match(r"(sched|rpc|ingest)\.", e.name):
                    rid = dict(e.stats).get("rid")
                    out.append((e.name, e.start_ns, e.end_ns, rid))
    return sorted(out, key=lambda s: s[1])


def test_every_span_is_on_the_host_plane(served):
    assert all(a["new_tokens"] == 6 for a in served["answers"])
    names = {h.name for h in trace.load(served["path"]).host}
    assert SPANS <= names, SPANS - names


def test_each_step_assembles_dispatches_fetches_selects_in_turn(served):
    tr = trace.load(served["path"])
    worker = sorted((h for h in tr.host if h.name in WORKER),
                    key=lambda h: h.start)
    for a, b in zip(worker, worker[1:]):
        assert a.end <= b.start, (a, b)              # none overlaps
    steps = [h.name for h in worker if h.name in STEP]
    assert len(steps) >= 4 * 6
    assert steps == STEP * (len(steps) // 4)


def test_a_request_keeps_its_rid_from_submit_to_retire(served):
    spans = _spans(served["path"])
    rids = {n: [r for m, _, _, r in spans if m == n]
            for n in ("sched.submit", "sched.admit", "sched.dense",
                      "sched.retire")}
    assert all(r is not None for v in rids.values() for r in v)
    assert len(rids["sched.submit"]) == 4
    assert len(rids["sched.dense"]) == 1 and len(rids["sched.admit"]) == 3
    assert sorted(rids["sched.admit"] + rids["sched.dense"]) == \
        sorted(rids["sched.submit"])
    assert sorted(rids["sched.retire"]) == sorted(rids["sched.admit"])


def test_step_counters(served):
    stats = served["impl"].batcher.stats
    assert stats["step_calls"] == len(served["calls"]) > 0
    assert stats["step_host_ns"] > 0
    got = served["inf"].Stats({})
    names = got["names"].split("\n")
    values = dict(zip(names, np.asarray(got["values"])))
    assert values["step_calls"] == len(served["calls"])
    assert values["step_host_ns"] > 0


def test_a_span_counts_its_time_and_lets_errors_through():
    from repro.core.spans import span
    stats = {"t": 0}
    with span("sched.test", stats, "t", rid=1):
        time.sleep(0.002)
    assert stats["t"] >= 2_000_000
    before = stats["t"]
    with pytest.raises(ValueError):
        with span("sched.test", stats, "t"):
            raise ValueError("propagates")
    assert stats["t"] > before
