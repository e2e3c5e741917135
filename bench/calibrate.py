#!/usr/bin/env python3
"""Readings that set the benchmark's limits, taken in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control 1,2,3] [--rates 1.0,2.0,3.0]

Set-up is paid once.  For each seed of ``--seeds`` the served process
gets that seed's weights, serves one window of the cell's traffic from
that seed, and the reference reads the window's sample as a run does; for
the seeds also in ``--control`` it reads, at the same positions, the gap
of the token the fp8 control puts first.  ``--rates`` instead serves the
open-loop mix at each rate, in the order given (first seed, plus one per
rate), reports what a run reports and whether the server sustained the
rate, and stops after the first rate it did not sustain.  Sustained
means: every request due in the window was answered, and latency did
not grow across the window: the median of each request's latency per
unit of its work (prefill chunks plus answer tokens, one step each) over
the requests due in the window's second half is at most ``GROWTH`` times
that of its first half.  One JSON line per reading goes to standard
output.  The benchmark's runs never call this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _checkout() -> None:
    """Import from this checkout, and keep JAX's compilation cache in it
    (read by JAX when it is first imported)."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


GROWTH = 1.5


def sustained(d, chunk: int) -> dict:
    """Whether one window's server kept up with its traffic (see above)."""
    import numpy as np
    from bench import cell
    by_idx = {r.idx: r for r in d.plan.requests}
    w0, w1 = d.window
    mid = (w0 + w1) / 2
    halves = ([], [])
    for r in d.records:
        if not r["m"] or not r["ok"]:
            continue
        req = by_idx[r["i"]]
        work = -(-req.prompt_len // chunk) + req.max_new
        halves[r["due"] >= mid].append((r["done"] - r["due"]) / work)
    first, second = (float(np.median(h)) if h else float("inf")
                     for h in halves)
    unanswered = sum(1 for r in d.records if r["m"] and not r["ok"])
    offered = sum(by_idx[r["i"]].max_new for r in d.records if r["m"]) / (
        w1 - w0)
    return {"unanswered": unanswered,
            "s_per_step_first_half": first, "s_per_step_second_half": second,
            "offered_tok_s": offered,
            "out_tok_s": cell.out_tokens_per_s(d),
            "sustained": unanswered == 0 and second <= GROWTH * first}


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def readings(served, seed: int, seconds: float, control: bool) -> dict:
    """One window from ``seed`` and its reference readings."""
    import numpy as np
    from bench import cell, check
    served.engine.params = served.new_weights(seed)
    d = served.drive(seed, seconds)
    served.engine.params = None
    gc.collect()
    got, ctl = cell.reference_gaps(served.dims, seed, d, control=control)
    checks = check.exact_checks(d.records, d.plan, seed, served.dims.vocab,
                                served.admitted)
    out = {"seed": seed, "requests": len(got),
           "tokens": int(sum(len(g) for g in got)),
           "logit_gap": cell.widest(got),
           "per_request": [float(np.max(g)) for g in got],
           "exact": {k: v["value"] for k, v in checks.items()},
           "compiles_in_window": d.compiles,
           "metrics": cell.end_to_end(d, seconds, 0.0)}
    if control:
        out["control_gap"] = cell.widest(ctl)
        out["control_per_request"] = [float(np.max(c)) for c in ctl]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control", type=_ints, default=[])
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    _checkout()
    from bench import cell, spec
    c = spec.load_cell(args.workload)
    device = cell.describe_devices()
    if device["platform"] != "tpu" or device["count"] < c.chips:
        print(f"needs {c.chips} TPU chip(s), found {device}", file=sys.stderr)
        return 1
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    served = cell.Served(c, args.seeds[0])
    served.warm()
    print(json.dumps({"setup_s": time.monotonic() - T_START,
                      "device": device}), flush=True)
    try:
        if args.rates:
            for k, rate in enumerate(float(r) for r in args.rates.split(",")):
                served.cell = dataclasses.replace(
                    c, traffic=dict(c.traffic, rate_per_s=rate))
                # a seed of its own per rate: prompts served at an earlier
                # rate would hit the prefix cache
                d = served.drive(args.seeds[0] + k, args.seconds)
                held = sustained(d, served.impl.batcher.prefill_chunk)
                late = max(d.late_s or [0.0])
                print(json.dumps({
                    "rate_per_s": rate,
                    "attempted": sum(r["m"] for r in d.records),
                    "failed": sum(r["m"] and not r["ok"] for r in d.records),
                    "gave_up": d.gave_up, "late_max_s": late,
                    "counters": d.counters, **held,
                    "metrics": cell.end_to_end(d, args.seconds, 0.0)}),
                    flush=True)
                if not held["sustained"]:
                    break
            return 0
        served.engine.params = None
        for seed in args.seeds:
            print(json.dumps(readings(served, seed, args.seconds,
                                      seed in args.control)), flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
