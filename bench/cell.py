"""One run of one cell: serve the configuration, drive its traffic through
page-encoded ``Infer``, check what came back, report the metrics.

The served process is built as ``repro.launch.serve`` builds it: an
``Engine`` (here handed the benchmark's seeded weights), an
``InferenceImpl`` with its ``PagedBatcher``, ``build_server``, TCP on
localhost.  The window drives ``Infer`` -> ``_admit_tokens`` ->
``PageIngest`` and the Bebop decode kernel -> ``PagedBatcher`` -> the
jitted ``paged_step`` / ``paged_step_verify`` -> the Pallas paged kernels.
The load generator (``loadgen.py``) is a process of its own.

Set-up warms every program the window will run and no other: the paged
step at each power-of-two block-table width up to the mix's longest
context, as a mixed step (chunk wide), a decode step (one wide) and a
verify step (``spec_len + 1`` wide), and the page decode at each prompt
width the mix can send.  Compilations are counted, and any inside the
window is printed.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import pathlib
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, flops, trace, traffic, weights
from .spec import BENCH, ROOT, Cell

TRACE_S = 4.0           # the traced part of a --trace 1 window, at most
TRACE_DIR = BENCH / ".trace"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    m = weights.dims(config)
    return ModelConfig(
        name=config["name"], family="dense", num_layers=m.layers,
        d_model=m.d, num_heads=m.heads, num_kv_heads=m.kv_heads,
        head_dim=m.head_dim, d_ff=m.ff, vocab_size=m.vocab,
        qkv_bias=m.qkv_bias, rope_theta=m.theta, norm_eps=m.eps,
        tie_embeddings=m.tied, dtype=m.dtype)


class Compiles:
    """Counts JAX's traces and compilations, with when each happened."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.at: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.at.append(time.monotonic())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.at if a <= t < b)


class StepRecorder:
    """Wraps the jitted paged steps.  While ``on``, records each step's
    kind, which rows are live, their positions and their valid-token
    counts, and marks the call with a host span in the trace."""

    def __init__(self):
        self.on = False
        self.steps: List[tuple] = []
        self.calls = 0

    def wrap(self, fn: Callable, kind: str) -> Callable:
        import jax

        def call(params, toks, pool, tables, pos, last):
            self.calls += 1
            if not self.on:
                return fn(params, toks, pool, tables, pos, last)
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                out = fn(params, toks, pool, tables, pos, last)
            width = kind if kind == "verify" else (
                "decode" if toks.shape[1] == 1 else "mixed")
            # whole arrays to the host: slicing on the device would compile
            self.steps.append((width, np.asarray(tables)[:, 0],
                               np.asarray(pos), np.asarray(last)))
            return out
        return call


@dataclasses.dataclass
class Drive:
    """What one window produced."""
    plan: traffic.Plan
    records: List[dict]
    window: tuple                   # monotonic instants it opened, closed
    counters: Dict[str, float]      # counter deltas over the window
    compiles: int                   # traces/compiles inside the window
    late_s: List[float]
    gave_up: bool
    traced: Optional[dict] = None   # the traced part, --trace 1 only


class Served:
    """The served process of one configuration, up and warmed."""

    def __init__(self, cell: Cell, seed: int, *, record_steps: bool = False):
        import jax
        from repro.serving import Engine, ServeConfig, build_server
        from repro.serving.service import InferenceImpl
        self.cell = cell
        self.dims = weights.dims(cell.config)
        self.compiles = Compiles()
        self.engine = Engine(model_config(cell.config),
                             ServeConfig(**cell.config["serve"]),
                             params=self.new_weights(seed))
        self.recorder = StepRecorder()
        if record_steps:
            step = self.recorder.wrap(self.engine.paged_step_fn(), "step")
            verify = self.recorder.wrap(self.engine.paged_verify_fn(),
                                        "verify")
            self.engine.paged_step_fn = lambda: step
            self.engine.paged_verify_fn = lambda: verify
        self.impl = InferenceImpl(self.engine)
        self.admitted: Dict[bytes, int] = {}
        submit = self.impl.batcher.submit

        def recording_submit(tokens, **kw):
            d = check.digest(tokens)
            self.admitted[d] = self.admitted.get(d, 0) + 1
            return submit(tokens, **kw)
        self.impl.batcher.submit = recording_submit
        self.server = build_server(self.engine, impl=self.impl)
        self.host, self.port, _ = self.server.listen_tcp()
        jax.block_until_ready(self.engine.params)

    def new_weights(self, seed: int):
        import jax
        p = weights.make(weights.key_of(seed), self.dims)
        return jax.block_until_ready(p)

    def counters(self) -> Dict[str, float]:
        out = {k: float(v) for k, v in self.impl.batcher.stats.items()}
        out.update({f"ingest_{k}": float(v)
                    for k, v in self.impl.ingest.stats.items()})
        return out

    # -- warm-up ------------------------------------------------------------
    def table_widths(self) -> List[int]:
        b = self.impl.batcher
        need = -(-traffic.max_context(self.cell.traffic) // b.cache.block_size)
        out, w = [], 1
        while True:
            out.append(min(w, b.cache.blocks_per_seq))
            if w >= need or w >= b.cache.blocks_per_seq:
                return out
            w *= 2

    def warm(self) -> None:
        """Compile (or load from the cache) every program the window runs."""
        import jax.numpy as jnp
        from repro.serving.service import encode_prompt_page
        b, sc = self.impl.batcher, self.engine.serve
        rows = b.max_batch
        shapes = [(self.engine.paged_step_fn(), b.prefill_chunk, True),
                  (self.engine.paged_step_fn(), 1, False)]
        if b.spec:
            shapes.append((self.engine.paged_verify_fn(), sc.spec_len + 1,
                           True))
        i32 = np.int32
        for w in self.table_widths():
            for fn, c, wide_pos in shapes:
                out, b.cache.pool = fn(
                    self.engine.params, jnp.asarray(np.zeros((rows, c), i32)),
                    b.cache.pool, jnp.asarray(np.zeros((rows, w), i32)),
                    jnp.asarray(np.zeros((rows, c if wide_pos else 1), i32)),
                    jnp.asarray(np.zeros((rows,), i32)))
                np.asarray(out)
        for width in traffic.prompt_widths(self.cell.traffic):
            self.impl._ensure_plan(width)
            page = encode_prompt_page(np.zeros((1, width), np.uint32))
            np.asarray(self.impl.ingest.admit(page).columns["tokens"])

    # -- the window -----------------------------------------------------------
    def drive(self, seed: int, seconds: float, *, traced: bool = False,
              child: Optional[subprocess.Popen] = None) -> Drive:
        """Run the cell's traffic for one window of ``seconds`` (with a
        load generator started earlier, or a new one)."""
        child = child or spawn_loadgen()
        try:
            return self._drive(child, seed, seconds, traced)
        finally:
            stop_process(child)

    def _drive(self, child, seed, seconds, traced) -> Drive:
        plan = traffic.plan(self.cell.traffic, seconds)
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("load generator did not start")
        child.stdin.write(json.dumps({
            "host": self.host, "port": self.port,
            "traffic": self.cell.traffic, "seed": seed, "seconds": seconds,
            "vocab": self.dims.vocab}) + "\n")
        child.stdin.close()
        t0 = float(json.loads(child.stdout.readline())["t0"])
        out: Dict[str, object] = {}
        reader = threading.Thread(
            target=lambda: out.update(json.loads(child.stdout.readline())),
            daemon=True)
        reader.start()
        w0, w1 = t0 + plan.window[0], t0 + plan.window[1]
        _sleep_until(w0)
        c0 = self.counters()
        info = None
        if traced:
            info = self._trace(w0, w1)
        _sleep_until(w1)
        c1 = self.counters()
        reader.join(timeout=w1 + traffic.GRACE_S + 120 - time.monotonic())
        if child.wait(timeout=30) != 0 or "records" not in out:
            raise RuntimeError(f"load generator failed (exit {child.poll()})")
        return Drive(plan=plan, records=out["records"], window=(w0, w1),
                     counters={k: c1[k] - c0.get(k, 0.0) for k in c1},
                     compiles=self.compiles.between(w0, w1),
                     late_s=out["late_s"], gave_up=bool(out["gave_up"]),
                     traced=info)

    def _trace(self, w0: float, w1: float) -> dict:
        """Trace the middle of the window; the steps it holds, the
        counters over it, the reduction of the trace."""
        span = min(TRACE_S, w1 - w0)
        _sleep_until(w0 + (w1 - w0 - span) / 2)
        rec = self.recorder
        rec.steps = []
        trace.start(str(TRACE_DIR))
        t_a = time.monotonic()
        rec.on = True
        c0 = self.counters()
        time.sleep(span)
        rec.on = False
        c1 = self.counters()
        calls = rec.calls
        # the last recorded step has finished once the next one starts
        deadline = time.monotonic() + 2.0
        while rec.calls == calls and time.monotonic() < deadline:
            time.sleep(0.001)
        t_b = time.monotonic()
        path = trace.stop(str(TRACE_DIR))
        return {"path": path, "window_ns": (t_b - t_a) * 1e9,
                "steps": list(rec.steps),
                "counters": {k: c1[k] - c0.get(k, 0.0) for k in c1}}

    def memory_peak(self) -> int:
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        log("device memory: " + json.dumps(stats))
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))

    def close(self) -> None:
        """Stop serving and free the program's device state."""
        self.server.drain(timeout=30.0)
        self.impl.batcher.close()
        self.impl.batcher.cache.pool = None
        self.engine.params = None
        self.compiles.close()
        gc.collect()


def _sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - time.monotonic()))


def spawn_loadgen() -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src")]
        + [p for p in [env.get("PYTHONPATH")] if p])
    return subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py")], env=env, text=True,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=str(ROOT))


def stop_process(p: subprocess.Popen) -> None:
    if p.poll() is None:
        p.kill()
    p.wait()
    for f in (p.stdin, p.stdout):
        if f is not None and not f.closed:
            f.close()


# -- the numbers ----------------------------------------------------------------


NO_ANSWER = 1e9         # the gap reported when no answer could be compared
LATE_S = 0.1            # a send later than this is flagged on stderr


def latencies(d: Drive) -> np.ndarray:
    """Seconds of every request of the window: from when it was due (open
    loop) or sent (closed loop) to its answer.  A request that failed or
    never came counts at the time the generator gave up on it."""
    end = d.window[1] + traffic.GRACE_S
    out = []
    for r in d.records:
        if not r["m"]:
            continue
        start = r["due"] if r["due"] is not None else r["sent"]
        out.append((r["done"] if r["ok"] else max(end, r["done"] or end))
                   - start)
    if not out:
        raise RuntimeError("no request was due in the window")
    return np.asarray(out)


def out_tokens_per_s(d: Drive) -> float:
    """Output tokens served per second of the window: every answered
    request of the run (ramp and tail too) adds its tokens in proportion
    to the share of its time, from sent to answered, that lies in the
    window.  A unary answer shows no token before the last one, so this
    is as close as a client sees the server's token rate; a request that
    failed adds nothing."""
    w0, w1 = d.window
    toks = 0.0
    for r in d.records:
        if not r["ok"] or r["n"] <= 0:
            continue
        span = max(r["done"] - r["sent"], 1e-9)
        inside = min(r["done"], w1) - max(r["sent"], w0)
        toks += r["n"] * max(0.0, inside) / span
    return toks / (w1 - w0)


def end_to_end(d: Drive, seconds: float, setup_s: float) -> Dict[str, float]:
    lat = latencies(d)
    return {"out_tok_s": out_tokens_per_s(d),
            "lat_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "lat_p90_ms": float(np.percentile(lat, 90)) * 1e3,
            "setup_s": setup_s}


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""
    cell: Cell
    dims: weights.Dims
    peak: dict
    reduced: Callable               # patterns -> trace.Reduced
    steps: List[tuple]              # (kind, table col 0, pos, last)
    counters: Dict[str, float]      # deltas over the traced part
    window_s: float


def reduce_trace(info: dict) -> Callable:
    """A memoizing reduction of the traced part's trace."""
    tr = trace.load(info["path"])
    cache: Dict[tuple, trace.Reduced] = {}

    def reduced(patterns: Optional[dict] = None) -> trace.Reduced:
        import re
        key = tuple(sorted((patterns or {}).items()))
        if key not in cache:
            cache[key] = trace.reduce(
                tr, (0.0, info["window_ns"]),
                {k: re.compile(v) for k, v in (patterns or {}).items()})
        return cache[key]
    return reduced


# -- one run ---------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float) -> dict:
    """One run of ``cell`` on the devices JAX finds (the caller has
    checked them); returns the result line's object."""
    device = describe_devices()
    peak = None
    if traced and device["platform"] != "cpu":   # a CPU run reads no peak
        from .spec import peaks
        peak = peaks(device["kind"])
    child = spawn_loadgen()      # its imports overlap the set-up
    try:
        served = Served(cell, seed, record_steps=traced)
        try:
            served.warm()
            log(f"set-up: {len(served.compiles.at)} traces/compiles before "
                "the window")
            d = served.drive(seed, seconds, traced=traced, child=child)
            device["memory_peak_bytes"] = served.memory_peak()
        finally:
            served.close()
    finally:
        stop_process(child)
    setup_s = d.window[0] - t_start
    late = np.asarray(d.late_s) if d.late_s else np.zeros(1)
    log(f"window: {len(d.records)} requests sent, "
        f"{sum(r['m'] for r in d.records)} in the window; "
        f"{d.compiles} traces/compiles inside the window; generator late "
        f"p50 {np.percentile(late, 50) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms; counters {json.dumps(d.counters)}")
    n_late = int((late > LATE_S).sum())
    if n_late:
        log(f"FLAG: the load generator sent {n_late} request(s) over "
            f"{LATE_S * 1e3:.0f} ms late (max {late.max() * 1e3:.3f} ms); "
            "their latency counts from when they were due")
    checks = check.exact_checks(d.records, d.plan, seed, served.dims.vocab,
                                served.admitted)
    gaps = reference_gaps(served.dims, seed, d)
    checks["logit_gap"] = {"value": widest(gaps[0]) if gaps else NO_ANSWER,
                           "limit": cell.config["limits"]["logit_gap"]}
    out = {"correct": check.verdict(checks),
           "attempted": int(sum(r["m"] for r in d.records)),
           "failed": int(sum(r["m"] and not r["ok"] for r in d.records))}
    if traced:
        info = d.traced
        ctx = Context(cell, served.dims, peak, reduce_trace(info),
                      info["steps"], info["counters"],
                      info["window_ns"] / 1e9)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        red = ctx.reduced()
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = red.window_ns / 1e9
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(d, seconds, setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    return out


def reference_gaps(m: weights.Dims, seed: int, d: Drive, *,
                   control: bool = False):
    """Per-token gaps of the window's sampled answers under the reference
    (and of the fp8 control's first tokens); None if nothing was answered.
    The program's device state must be freed first."""
    from . import reference
    chosen = check.sample(d.records, d.plan, seed)
    if not chosen:
        return None
    params = weights.make(weights.key_of(seed), m)
    got, ctl = reference.gaps_of(
        params, m, check.pairs(chosen, d.plan, seed, m.vocab),
        control=control)
    del params
    gc.collect()
    log(f"reference: {len(chosen)} requests, "
        f"{sum(len(g) for g in got)} served tokens compared")
    return got, ctl


def widest(gaps) -> float:
    return float(max(float(np.max(g)) for g in gaps))


def describe_devices() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
