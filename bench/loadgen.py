"""The load generator: drives page-encoded ``Infer`` over TCP, in a
process of its own that never initializes an accelerator.

The parent starts it early (its imports overlap the server's set-up);
it prints ``ready`` once imported, and the parent then writes one JSON
line to its standard input:

    {"host", "port", "traffic": {...}, "seed", "seconds", "vocab"}

It encodes every request's page before the traffic starts (so a send
does no work but the call), freezes the garbage collector's view of what
exists by then, and prints ``{"t0": ...}``: the ``time.monotonic()``
instant the traffic starts at (the clock is the machine's, shared by
both processes).  At the end it prints one JSON line: every request
sent, with when it was due, sent and answered, and the tokens of each
answer in the measured window.

Open loop: each request is sent at its due time on a thread of its own,
whatever the server is doing, so a stall makes later requests wait and
their latency, timed from when they were due, shows it.  Closed loop:
each client sends its next request when its answer returns.  Either way
traffic goes on after the window closes until every request of the
window is answered, or ``traffic.GRACE_S`` has passed; then every
connection is closed, which makes the server drop what it still holds.
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import queue
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import traffic  # noqa: E402

RPC_TIMEOUT_S = 300.0
START_S = 0.2       # from reporting t0 to the first request


class _Channels:
    """Idle TCP channels, reused; every one is closed at the end."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle: "queue.Queue" = queue.Queue()
        self.all = []
        self.lock = threading.Lock()
        self.closed = False

    def take(self):
        with self.lock:
            if self.closed:
                raise ConnectionError("load generator stopped")
        try:
            return self.idle.get_nowait()
        except queue.Empty:
            from repro.core.rpc import Channel, TcpTransport
            ch = Channel(TcpTransport.connect(self.host, self.port))
            with self.lock:
                self.all.append(ch)
            return ch

    def close(self):
        with self.lock:
            self.closed = True
            chans = list(self.all)
        for ch in chans:
            try:
                ch.close()
            except Exception:  # noqa: BLE001 - closing is best effort
                pass


def run(cfg: dict, started=lambda t0: None) -> dict:
    """Serve one plan; ``started(t0)`` is called once every page is
    encoded, with the instant the traffic starts at."""
    from repro.serving.service import (InferenceService, decode_token_page,
                                       encode_prompt_page)
    seed = int(cfg["seed"])
    plan = traffic.plan(cfg["traffic"], float(cfg["seconds"]))
    pages = {r.idx: encode_prompt_page(
        traffic.prompt_tokens(seed, r, int(cfg["vocab"])))
        for r in plan.requests}
    gc.collect()
    gc.freeze()
    t0 = time.monotonic() + START_S
    started(t0)
    chans = _Channels(cfg["host"], int(cfg["port"]))
    w1 = t0 + plan.window[1]
    records = {}
    lock = threading.Lock()
    # window requests not yet answered (open loop: known from the start)
    open_measured = {r.idx for r in plan.requests
                     if plan.loop == "open" and plan.measured(r, 0.0)}
    window_shut = threading.Event()   # no more requests join the window
    stop = threading.Event()

    def maybe_stop():
        if window_shut.is_set() and not open_measured:
            stop.set()

    def call(req, due):
        if stop.is_set():
            return
        page = pages[req.idx]
        sent = time.monotonic()
        measured = plan.measured(req, sent - t0)
        rec = {"i": req.idx, "c": req.client, "due": due, "sent": sent,
               "done": None, "ok": False, "n": 0, "m": measured}
        with lock:
            records[req.idx] = rec
            if measured:
                open_measured.add(req.idx)
        try:
            ch = chans.take()
            res = ch.typed(InferenceService).Infer(
                {"page": page, "max_new_tokens": req.max_new},
                timeout=RPC_TIMEOUT_S)
            out = decode_token_page(bytes(bytearray(res["page"])))
            rec["done"] = time.monotonic()
            rec["ok"] = True
            rec["n"] = int(out.shape[1]) if out.size else 0
            if measured:
                rec["tok"] = out.reshape(-1).astype(int).tolist()
            chans.idle.put(ch)
        except Exception as e:  # noqa: BLE001 - a failed request is recorded
            rec["done"] = time.monotonic()
            rec["err"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            with lock:
                open_measured.discard(req.idx)
                maybe_stop()

    def shut_window_at(t):
        time.sleep(max(0.0, t - time.monotonic()))
        with lock:
            window_shut.set()
            maybe_stop()

    threading.Thread(target=shut_window_at, args=(w1,), daemon=True).start()
    workers = []
    if plan.loop == "open":
        pool = ThreadPoolExecutor(max_workers=256)
        for req in plan.requests:
            due = t0 + req.due
            while not stop.is_set() and time.monotonic() < due:
                stop.wait(min(0.05, max(0.0, due - time.monotonic())))
            if stop.is_set():
                break
            pool.submit(call, req, due)
        stop.wait(max(0.0, w1 + traffic.GRACE_S - time.monotonic()))
        chans.close()
        pool.shutdown(wait=True)
    else:
        by_client = [[r for r in plan.requests if r.client == c]
                     for c in range(plan.clients)]

        def client(reqs):
            time.sleep(max(0.0, t0 - time.monotonic()))
            for req in reqs:
                if stop.is_set():
                    return
                call(req, None)

        for reqs in by_client:
            t = threading.Thread(target=client, args=(reqs,), daemon=True)
            t.start()
            workers.append(t)
        stop.wait(max(0.0, w1 + traffic.GRACE_S - time.monotonic()))
        stop.set()
        chans.close()
        for t in workers:
            t.join(timeout=RPC_TIMEOUT_S)
    late = [r["sent"] - r["due"] for r in records.values()
            if r["due"] is not None]
    return {"records": sorted(records.values(), key=lambda r: r["i"]),
            "late_s": late, "gave_up": not window_shut.is_set()
            or bool(open_measured)}


def main() -> int:
    import repro.serving.service  # noqa: F401 - import before saying ready
    sys.stdout.write("ready\n")
    sys.stdout.flush()
    cfg = json.loads(sys.stdin.readline())

    def started(t0):
        sys.stdout.write(json.dumps({"t0": t0}) + "\n")
        sys.stdout.flush()
    out = run(cfg, started)
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
