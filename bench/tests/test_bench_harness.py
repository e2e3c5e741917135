"""The harness: it refuses to measure without a TPU, finds a cell defined
only under a test directory, and runs a cell's whole body on a CPU."""
import json
import os
import subprocess
import sys

import numpy as np

from bench import check, spec, traffic
from bench.tests import tiny


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=str(spec.ROOT))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads(spec.BENCHMARK.read_text())
    for w in bench["workloads"]:
        c = spec.load_cell(w["name"])
        assert c.config["name"] == w["config"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        for m in c.per_layer:
            assert callable(c.reader(m["name"]))
        # a request never outgrows the cache, and every width fits the cap
        assert traffic.max_context(c.traffic) <= c.config["serve"][
            "cache_len"]


def test_a_cell_defined_only_under_a_test_directory():
    c = tiny.load()
    assert c.name == tiny.NAME and c.config["name"] == "tiny"
    assert c.traffic["loop"] == "open"
    names = [m["name"] for m in c.per_layer]
    assert "spec_accept_share" in names           # a metric of its own
    assert c.reader("spec_accept_share")(
        type("C", (), {"counters": {"spec_proposed": 4.0,
                                    "spec_accepted": 3.0}})) == 75.0


def test_the_cell_body_on_a_cpu():
    out = tiny.run(20261016)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"out_tok_s", "lat_p50_ms", "lat_p90_ms",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    traced = tiny.run(20261017, traced=True)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["rows_per_step"]["value"] >= 1
    assert "spec_accept_share" in traced["metrics"]


def test_ingest_check_counts_a_prompt_never_received():
    c = tiny.load()
    p = traffic.plan(c.traffic, 2)
    recs = [{"i": r.idx, "m": True, "ok": True, "n": r.max_new}
            for r in p.requests[:3]]
    admitted = {check.digest(traffic.prompt_tokens(5, r, 512)): 1
                for r in p.requests[:3]}
    assert check.exact_checks(recs, p, 5, 512, admitted)[
        "ingest_mismatch"]["value"] == 0
    admitted.pop(next(iter(admitted)))
    bad = check.exact_checks(recs, p, 5, 512, admitted)
    assert bad["ingest_mismatch"]["value"] == 1 and not check.verdict(bad)
    recs[0]["n"] -= 1
    assert check.exact_checks(recs, p, 5, 512, {})["wrong_length"][
        "value"] == 1
    np.testing.assert_array_equal(check.sample([], p, 5), [])


def test_out_tok_s_counts_each_answer_by_its_time_in_the_window():
    from bench import cell
    recs = [
        # wholly inside [10, 20): all 30 tokens
        {"i": 0, "m": True, "ok": True, "n": 30, "sent": 11.0, "done": 15.0},
        # half of its time inside: 20 of 40 tokens
        {"i": 1, "m": False, "ok": True, "n": 40, "sent": 5.0, "done": 15.0},
        # a quarter inside: 10 of 40
        {"i": 2, "m": True, "ok": True, "n": 40, "sent": 19.0, "done": 23.0},
        # failed, or wholly outside: nothing
        {"i": 3, "m": True, "ok": False, "n": 0, "sent": 12.0, "done": 30.0},
        {"i": 4, "m": False, "ok": True, "n": 50, "sent": 1.0, "done": 9.0},
    ]
    d = cell.Drive(plan=None, records=recs, window=(10.0, 20.0),
                   counters={}, compiles=0, late_s=[], gave_up=False)
    assert cell.out_tokens_per_s(d) == (30 + 20 + 10) / 10.0
