"""The one traffic generator: a traffic file's parameters + a seed -> requests.

A traffic file (``bench/traffic/<name>.json``) gives:

- ``loop``: ``"open"`` (independent users: Poisson arrivals at
  ``rate_per_s``) or ``"closed"`` (``clients`` callers, each sending its
  next request when its answer returns);
- ``ramp_s``: seconds of traffic before the measured window opens, so the
  window sees a steady state; ``tail_s``: seconds of scheduled traffic
  after it closes, so load stays on until the window's requests finish;
- ``prompt`` and ``output``: length distributions, each
  ``{"median", "sigma", "min", "max"}`` of a lognormal, clipped, and for
  prompts ``round_up``: every prompt length is rounded up to a multiple of
  it, which bounds the set of prompt widths the server sees;
- ``schedule_seed``: the sizes and the arrivals are one draw of the
  mix, the same for every run: stratified samples (quantiles at
  (k + 0.5) / n) in an order drawn from this seed.  Open loop: the ramp,
  the window and the tail each get ``round(rate * seconds)`` requests,
  with exponential gaps (stratified too) scaled to fill the span exactly.
  Closed loop: round k (the k-th request of every client) is one
  stratified sample over the clients.  A window holds a dozen or a few
  dozen requests of heavy-tailed sizes; were they redrawn per seed, which
  request is long would move the metrics more than the server does.

``--seed`` draws the prompts' tokens (uniform over the vocabulary,
unshared) and the weights.  Every request is greedy, with no stop
token: its answer has exactly ``max_new`` tokens.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional, Tuple

import numpy as np

_Z = statistics.NormalDist()
#: how long after the window closes its requests may still be answered
GRACE_S = 120.0


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    client: int               # closed loop: the caller; open loop: -1
    due: Optional[float]      # open loop: seconds after traffic start
    prompt_len: int
    max_new: int


@dataclasses.dataclass(frozen=True)
class Plan:
    loop: str
    requests: Tuple[Request, ...]
    window: Tuple[float, float]   # seconds after traffic start
    clients: int

    def measured(self, req: Request, sent: float) -> bool:
        """Whether ``req`` belongs to the window: open loop by when it was
        due, closed loop by when it was sent (seconds after start)."""
        t = req.due if self.loop == "open" else sent
        return self.window[0] <= t < self.window[1]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, *stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(dist: dict, q: np.ndarray) -> np.ndarray:
    z = np.array([_Z.inv_cdf(float(p)) for p in q])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.ceil(x), dist["min"], dist["max"])
    step = int(dist.get("round_up", 1))
    return (np.ceil(x / step) * step).astype(int)


def prompt_widths(spec: dict) -> List[int]:
    """Every prompt length the mix can send."""
    p = spec["prompt"]
    step = int(p.get("round_up", 1))
    lo = int(math.ceil(p["min"] / step) * step)
    hi = int(math.ceil(p["max"] / step) * step)
    return list(range(lo, hi + 1, step))


def max_context(spec: dict) -> int:
    """The longest prompt plus the longest answer."""
    return prompt_widths(spec)[-1] + int(spec["output"]["max"])


def _stratified(spec: dict, n: int, stream: int):
    """``n`` (prompt, output, gap) triples: one stratified sample of each
    distribution (quantiles at (k + 0.5) / n; gaps exponential), each in
    an order drawn from the mix's ``schedule_seed``."""
    rng = _rng(int(spec["schedule_seed"]), stream)
    q = _quantiles(n)
    return (rng.permutation(_lengths(spec["prompt"], q)),
            rng.permutation(_lengths(spec["output"], q)),
            rng.permutation(-np.log1p(-q)))


def window_span(window: Tuple[float, float]) -> Tuple[float, float]:
    return window[0], window[1] - window[0]


def plan(spec: dict, seconds: float) -> Plan:
    """The requests of one run of ``seconds`` measured seconds."""
    ramp, tail = float(spec["ramp_s"]), float(spec["tail_s"])
    window = (ramp, ramp + float(seconds))
    if spec["loop"] == "open":
        rate = float(spec["rate_per_s"])
        reqs: List[Request] = []
        for k, (start, length) in enumerate(
                ((0.0, ramp), window_span(window), (window[1], tail))):
            n = max(1, int(round(rate * length)))
            p, o, g = _stratified(spec, n, k)
            due = start + (np.cumsum(g) - g) * (length / g.sum())
            reqs.extend(Request(len(reqs), -1, float(t), int(a), int(b))
                        for t, a, b in zip(due, p, o))
        return Plan("open", tuple(reqs), window, 0)
    if spec["loop"] == "closed":
        c = int(spec["clients"])
        per = int(spec["per_client"])
        reqs = []
        for k in range(per):
            # round k: the k-th request of every client, one stratified set
            p, o, _ = _stratified(spec, c, k)
            reqs.extend(Request(len(reqs), i, None, int(p[i]), int(o[i]))
                        for i in range(c))
        reqs = tuple(reqs)
        return Plan("closed", reqs, window, c)
    raise ValueError(f"unknown loop {spec['loop']!r}")


def prompt_tokens(seed: int, req: Request, vocab: int) -> np.ndarray:
    """The [1, prompt_len] uint32 prompt of one request."""
    return _rng(seed, 1, req.idx).integers(
        0, vocab, (1, req.prompt_len), dtype=np.uint32)
