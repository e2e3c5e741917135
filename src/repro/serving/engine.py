"""Serving engine: jitted prefill/decode steps + request scheduling.

`prefill_step` and `decode_step` here are exactly what the multi-pod
dry-run lowers for the inference shapes (prefill_32k / decode_32k /
long_500k): one new token against a KV cache (or recurrent state) of
``seq_len``.

Two schedulers sit in front of the engine:

  * :class:`ContinuousBatcher` — the dense-cache scheduler: concurrent
    requests with *compatible shapes* (same prompt length, same stop
    token) are concatenated along the batch axis and run as ONE
    prefill+decode sequence.  Kept as the fallback for model families
    without paged-KV support and as the benchmark baseline.
  * :class:`PagedBatcher` — the block-pooled scheduler
    (serving/kv_cache.py + the paged-attention kernel): every request's
    KV lives in fixed-stride blocks addressed through a block table, so
    one decode step advances a batch of *mixed-length* rows, prompts are
    prefilled in fixed-size chunks, and new requests are admitted into
    free batch slots mid-generation instead of waiting for a
    shape-compatible group.

Both shed expired requests at admission and before device work.
"""
from __future__ import annotations

import collections
import concurrent.futures as _cf
import dataclasses
import itertools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.spans import span
from ..models import get_model
from .kv_cache import (CacheOOM, PagedKVCache, aligned_block_size,
                       block_keys, ring_geometry)
from .sampling import (GREEDY, GenerationParams, SamplingParams,
                       rejection_sample, sample_tokens, spec_uniforms,
                       target_probs)
from .spec import ngram_propose

_log = logging.getLogger(__name__)

# one assembled device step: the jitted function, its batch arrays
# (toks, tables, pos, last), and what takes the step's host logits
_Batch = Tuple[Callable, tuple, Callable[[np.ndarray], None]]


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    cache_len: int = 1024
    max_new_tokens: int = 64
    # default sampling for requests that don't carry their own
    # GenerationParams fields (serving/sampling.py): temperature 0 =
    # greedy argmax (bit-identical to the pre-sampling engine), top_k 0 /
    # top_p 1.0 disable the filters, seed feeds the per-request folded
    # PRNG keys so sampled output is reproducible and independent of
    # batch composition
    temperature: float = 0.0    # 0 = greedy
    top_k: int = 0              # 0 = no top-k filter
    top_p: float = 1.0          # 1.0 = no nucleus (top-p) filter
    seed: int = 0               # base PRNG seed for sampled requests
    # paged KV cache (serving/kv_cache.py); paged=True routes supported
    # model families through PagedBatcher, others fall back to the dense
    # ContinuousBatcher automatically
    paged: bool = True
    block_size: int = 16        # tokens per KV block (64B-alignment rounds up)
    prefill_chunk: int = 32     # prompt tokens prefilled per chunked step
    num_blocks: int = 0         # 0 = auto: max_batch * blocks_per_seq + null
    # fused prefill/decode scheduling: admission installs a
    # prefill-in-progress row and the scheduler interleaves its chunks
    # with decode steps, so admitting a long prompt never stalls in-flight
    # generations.  False restores the blocking prefill loop (benchmark
    # baseline / bisection escape hatch).
    fused_prefill: bool = True
    # per-step budget of NEW tokens a fused step may process (decode rows
    # count 1 each; prefilling rows share the remainder, clamped to
    # prefill_chunk).  0 = no budget: every prefilling row advances a
    # full chunk per step.
    max_step_tokens: int = 0
    # automatic prefix caching (serving/kv_cache.py PrefixCache): new
    # prompts are matched block-by-block against already-resident
    # prefixes, matched blocks are shared (refcounted, copy-on-write on
    # conflict) and their prefill is skipped entirely.
    prefix_cache: bool = True
    # cap on cached-but-unreferenced prefix blocks kept resident between
    # requests (the LRU).  0 = bounded only by the pool: idle cached
    # blocks are evicted on demand when an allocation runs short.
    prefix_lru_blocks: int = 0
    # self-speculative decoding (serving/spec.py): when every active row
    # is decoding, an n-gram lookup drafter over each row's OWN
    # prompt+output proposes up to spec_len continuation tokens, and ONE
    # verify step (the fused paged-prefill path, all drafted positions
    # scored at once) advances accepted prefixes several tokens per step.
    # The verifier's argmax is authoritative, so emitted tokens are
    # bit-identical to non-speculative greedy decode; a rejected draft is
    # rolled back by simply not committing its positions.
    spec_decode: bool = True
    spec_len: int = 4           # max drafted tokens per request per step
    spec_ngram: int = 2         # shortest suffix n-gram worth drafting from
    # SLO-aware scheduling (PagedBatcher only).  swap=True pages the KV
    # blocks of lowest-priority victims out to host memory under pool
    # pressure (admission or copy-on-write) instead of shedding; a
    # preempted request resumes token-identically once blocks free up.
    swap: bool = True
    default_priority: int = 0   # priority class when submit() passes none;
    # higher wins, preemption only ever claims strictly-lower victims
    ttft_slo_ms: float = 0.0    # default time-to-first-token target (0=off)
    tpot_slo_ms: float = 0.0    # default inter-token latency target (0=off)
    # scheduler steps between SLO-controller updates: the controller
    # nudges the live max_step_tokens budget toward whichever of
    # TTFT/TPOT the recent window violates more
    slo_adjust_every: int = 16


class Engine:
    """Single-model serving engine with greedy/temperature sampling."""

    def __init__(self, cfg: ModelConfig, serve_cfg: ServeConfig,
                 params: Optional[Any] = None, *, seed: int = 0):
        self.cfg = cfg
        self.serve = serve_cfg
        self.model = get_model(cfg)
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        self.params = params
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, serve_cfg.cache_len))
        self._decode = jax.jit(self.model.decode_step,
                               donate_argnums=(2,))
        self._paged_step = None    # compiled lazily by PagedBatcher
        self._paged_verify = None  # the multi-logit speculative verifier
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens_out": 0}

    @property
    def supports_paged(self) -> bool:
        return bool(getattr(self.model, "supports_paged", False))

    def paged_step_fn(self):
        """The jitted paged step (pool donated so updates are in place)."""
        if self._paged_step is None:
            self._paged_step = jax.jit(self.model.paged_step,
                                       donate_argnums=(2,))
        return self._paged_step

    def paged_verify_fn(self):
        """The jitted speculative verify step: same fused paged-prefill
        body as :meth:`paged_step_fn`, but logits at every position."""
        if self._paged_verify is None:
            self._paged_verify = jax.jit(self.model.paged_step_verify,
                                         donate_argnums=(2,))
        return self._paged_verify

    # -- generation --------------------------------------------------------------
    def generate(self, tokens: np.ndarray, *, max_new_tokens: Optional[int]
                 = None, stop_token: Optional[int] = None,
                 deadline=None, start_from: int = 0, on_token=None,
                 sampling: Optional[SamplingParams] = None) -> np.ndarray:
        """Greedy or sampled generation.  tokens: [B, T] prompt.

        ``start_from``: number of already-delivered tokens to skip (the RPC
        stream-cursor resume path: the handler re-generates deterministically
        and skips past what the client already has — sampled requests stay
        resumable because the folded-key schedule makes their draws a pure
        function of (seed, output index, row)).

        ``sampling`` (default greedy) picks each token with
        :func:`~repro.serving.sampling.sample_tokens`; row ``r`` of the
        batch is candidate ``r`` of the key schedule, matching the paged
        engine's fork numbering so paged and dense agree token-for-token
        at the same seed.
        """
        cfg, sc = self.cfg, self.serve
        sp = GREEDY if sampling is None else sampling
        maxn = sc.max_new_tokens if max_new_tokens is None else max_new_tokens
        b, t = tokens.shape
        batch = self._prefill_batch(tokens)
        logits, cache = self._prefill(self.params, batch)
        self.stats["prefills"] += 1
        out: List[np.ndarray] = []
        pos = t
        next_tok = self._pick(logits, sp, 0)
        for i in range(maxn):
            if deadline is not None and deadline.expired():
                break
            if i >= start_from:
                out.append(next_tok)
                if on_token is not None:
                    on_token(i, next_tok)
            logits, cache = self._decode(self.params, next_tok, cache,
                                         jnp.int32(pos))
            self.stats["decode_steps"] += 1
            pos += 1
            next_tok = self._pick(logits, sp, i + 1)
            if stop_token is not None and bool((next_tok == stop_token).all()):
                break
        self.stats["tokens_out"] += sum(o.shape[1] for o in out) * b
        result = np.concatenate(out, axis=1) if out else \
            np.zeros((b, 0), np.int32)
        return result

    @staticmethod
    def _pick(logits, sp: SamplingParams, index: int) -> np.ndarray:
        """Next token column [B, 1] — the original argmax lines when
        greedy (bit-identical by construction), the seeded sampler
        otherwise."""
        if sp.greedy:
            return np.asarray(jnp.argmax(logits, -1), np.int32)[:, None]
        return sample_tokens(logits, sp, index=index)[:, None]

    def _prefill_batch(self, tokens: np.ndarray) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.input_kind == "frames":
            b, t = tokens.shape
            frames = np.zeros((b, max(t // cfg.frame_ratio, 1), cfg.d_model),
                              np.float32)
            return {"frames": frames, "tokens": tokens}
        if cfg.input_kind == "embeddings":
            raise NotImplementedError(
                "vlm serving requires precomputed embeddings; use "
                "generate_from_embeds")
        return {"tokens": tokens}

    # -- scoring (used by the batch-pipelining example: embed -> generate ->
    #    score chains in one RPC round trip) -----------------------------------
    def score(self, tokens: np.ndarray) -> np.ndarray:
        """Mean log-prob of each sequence under the model.  [B, T] -> [B]."""
        batch = {"tokens": tokens[:, :-1]}
        if self.cfg.input_kind == "frames":
            b, t = tokens.shape
            batch["frames"] = np.zeros(
                (b, max(t // self.cfg.frame_ratio, 1), self.cfg.d_model),
                np.float32)
        logits = jax.jit(self.model.logits)(self.params, batch)
        lf = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            lf, jnp.asarray(tokens[:, 1:])[..., None], axis=-1)[..., 0]
        return np.asarray(jnp.mean(picked, axis=-1))


# --------------------------------------------------------------------------
# Continuous batching
# --------------------------------------------------------------------------


class ShedError(RuntimeError):
    """Request dropped by the scheduler (queue overflow or expired deadline)."""


def _config_sampling(sc: ServeConfig) -> SamplingParams:
    """The ServeConfig-default sampling for requests that pass none."""
    return SamplingParams(temperature=sc.temperature, top_k=sc.top_k,
                          top_p=sc.top_p, seed=sc.seed)


@dataclasses.dataclass(eq=False)   # identity semantics: queues/slot lists
class _Pending:                    # look these up with `in` / `.remove()`,
    """One admitted request group: [B, T] prompt rows awaiting assembly."""

    tokens: np.ndarray
    max_new_tokens: int
    stop_token: Optional[int]
    deadline: Optional[Any]
    future: _cf.Future
    sampling: SamplingParams = GREEDY
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    cancelled: bool = False     # caller gone (connection died): stop paying

    @property
    def rows(self) -> int:
        return self.tokens.shape[0]

    @property
    def seq_len(self) -> int:
        return self.tokens.shape[1]

    def expired(self) -> bool:
        # cancelled rides the expiry path: every shed sweep that reclaims
        # an expired request's resources reclaims a cancelled one's too
        return self.cancelled or (
            self.deadline is not None and self.deadline.expired())


class ContinuousBatcher:
    """Admission queue + batch assembly in front of a single Engine.

    Requests are submitted from RPC handler threads and resolved by one
    worker thread.  Assembly greedily merges queued requests that share a
    prompt length and stop token (prefill is shape-polymorphic only across
    the batch axis) up to ``max_batch`` rows, waiting at most ``window_s``
    for stragglers once the first request is in hand — the classic
    throughput/latency knob.  Deadlines shed work twice: on submit (full
    queue or already expired) and again at assembly, so an expired request
    never reaches the device.
    """

    def __init__(self, engine: Engine, *, max_batch: Optional[int] = None,
                 max_queue: int = 64, window_s: float = 0.005):
        self.engine = engine
        self.max_batch = max_batch or engine.serve.max_batch
        self.max_queue = max_queue
        self.window_s = window_s
        self._queue: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False  # guarded by _cond
        self.stats = {"requests": 0, "rows": 0, "batches": 0,
                      "batched_rows": 0, "shed": 0, "worker_errors": 0,
                      "cancelled": 0, "sampled_requests": 0}
        self._worker_error_logged = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="serve-batcher")
        self._worker.start()

    # -- admission ----------------------------------------------------------
    def submit(self, tokens: np.ndarray, *,
               params: Optional[GenerationParams] = None,
               max_new_tokens: Optional[int] = None,
               stop_token: Optional[int] = None,
               deadline=None, priority: Optional[int] = None,
               ttft_slo_ms: Optional[float] = None,
               tpot_slo_ms: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               n: int = 1) -> _cf.Future:
        """Queue a [B, T] (or [T]) prompt; resolves to [B, new] int32.

        ``params`` (a validated :class:`GenerationParams`) supplies every
        per-request field at once and overrides the flat keyword
        spellings, which are kept working for direct callers.
        ``priority``/``ttft_slo_ms``/``tpot_slo_ms`` are accepted for
        interface parity with :meth:`PagedBatcher.submit` and ignored:
        the dense scheduler has no preemption tier (a request's cache is
        a monolithic tensor, not swappable blocks), so priorities cannot
        change its FIFO shape-merging order.

        ``sampling`` (default: the ServeConfig sampling fields) draws
        each token with the seeded sampler; sampled requests are never
        shape-merged with other requests, so their tokens stay
        independent of batch composition.  ``n > 1`` (single-row prompt
        only) generates n candidates by replicating the prompt across
        the batch axis — the dense cache has no block sharing, so unlike
        the paged fork this pays the prompt's KV n times, and a
        ``stop_token`` ends the group only when every candidate has
        emitted it (the dense lockstep rule).
        """
        if params is not None:
            params.validate()
            max_new_tokens = params.max_new_tokens
            stop_token = params.stop_token
            sampling = params.sampling(self.engine.serve)
            n = params.n
        del priority, ttft_slo_ms, tpot_slo_ms
        tokens = np.atleast_2d(np.asarray(tokens, dtype=np.int32))
        sp = _config_sampling(self.engine.serve) if sampling is None \
            else sampling
        n = max(1, int(n))
        if n > 1:
            if tokens.shape[0] != 1:
                raise ValueError(
                    f"n={n} parallel sampling needs a single-row prompt, "
                    f"got batch {tokens.shape[0]}")
            tokens = np.repeat(tokens, n, axis=0)
        maxn = self.engine.serve.max_new_tokens if max_new_tokens is None \
            else max_new_tokens  # explicit 0 = prefill-only, not the default
        p = _Pending(tokens, maxn, stop_token, deadline, _cf.Future(),
                     sampling=sp)
        with self._cond:
            if self._closed:
                self.stats["shed"] += 1
                p.future.set_exception(ShedError("batcher closed"))
                return p.future
            if p.expired():
                self.stats["shed"] += 1
                p.future.set_exception(
                    ShedError("deadline expired before admission"))
                return p.future
            if len(self._queue) >= self.max_queue:
                self.stats["shed"] += 1
                p.future.set_exception(ShedError("admission queue full"))
                return p.future
            self._queue.append(p)
            self.stats["requests"] += 1
            self.stats["rows"] += p.rows
            if not sp.greedy:
                self.stats["sampled_requests"] += 1
            self._cond.notify()
        return p.future

    def generate(self, tokens: np.ndarray, **kw) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(tokens, **kw).result()

    def cancel(self, fut: _cf.Future) -> bool:
        """Drop the queued request owning ``fut`` (caller's connection died).

        Dense-path scope: only *queued* requests can be abandoned — once a
        group is assembled its cache is one monolithic tensor mid-kernel,
        so an executing request runs to completion (its result is simply
        discarded).  Returns True if the request was found and cancelled.
        """
        with self._cond:
            for p in self._queue:
                if p.future is fut:
                    p.cancelled = True
                    self.stats["cancelled"] += 1
                    self._cond.notify_all()
                    return True
        return False

    # -- assembly -----------------------------------------------------------
    def _take_group(self, timeout: Optional[float]) -> Optional[_Pending]:
        """Pop the first live request, shedding expired ones in place."""
        with self._cond:
            end = None if timeout is None else time.monotonic() + timeout
            while True:
                while self._queue:
                    p = self._queue.popleft()
                    if p.expired():
                        self.stats["shed"] += 1
                        p.future.set_exception(
                            ShedError("deadline expired in queue"))
                        continue
                    return p
                if self._closed:
                    return None
                remaining = None if end is None else end - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                self._cond.wait(remaining)

    def _take_compatible(self, head: _Pending) -> List[_Pending]:
        """Merge queued requests matching ``head`` up to max_batch rows."""
        group = [head]
        rows = head.rows
        cutoff = time.monotonic() + self.window_s
        while rows < self.max_batch:
            with self._cond:
                found = None
                shed = False
                for p in self._queue:
                    if p.expired():
                        self._queue.remove(p)
                        self.stats["shed"] += 1
                        p.future.set_exception(
                            ShedError("deadline expired in queue"))
                        shed = True
                        break  # deque mutated mid-iteration; rescan
                    if p.seq_len == head.seq_len \
                            and p.stop_token == head.stop_token \
                            and head.sampling.greedy and p.sampling.greedy \
                            and rows + p.rows <= self.max_batch:
                        # sampled requests run solo: merging would shift
                        # their row indices in the shared batch and make
                        # the emitted tokens depend on batch composition
                        found = p
                        break
                if found is not None:
                    self._queue.remove(found)
                    group.append(found)
                    rows += found.rows
                    continue
                if shed:
                    continue  # don't burn the window waiting; rescan now
                remaining = cutoff - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
        return group

    def _run(self) -> None:
        while True:
            head = self._take_group(None)
            if head is None:
                return
            group = self._take_compatible(head)
            try:
                self._execute(group)
            except Exception:  # noqa: BLE001 - the worker must survive
                # _execute fails futures itself; anything escaping here
                # (e.g. InvalidStateError from a racing cancel) must not
                # kill the only worker thread — but a silent infinite
                # retry is unobservable, so count it and log the first.
                self.stats["worker_errors"] += 1
                if not self._worker_error_logged:
                    self._worker_error_logged = True
                    _log.exception(
                        "ContinuousBatcher worker step raised; continuing "
                        "(further escapes counted in stats['worker_errors'])")
                continue

    def _execute(self, group: List[_Pending]) -> None:
        tokens = np.concatenate([p.tokens for p in group], axis=0) \
            if len(group) > 1 else group[0].tokens
        maxn = max(p.max_new_tokens for p in group)
        # Run to the LATEST FINITE member deadline: early members get
        # their full generation, and when the cutoff lands mid-batch the
        # slicing loop below hands every member whatever prefix was
        # generated by then (an earlier-deadline member keeps tokens past
        # its own cutoff — surplus, never missing work).  A member
        # WITHOUT a deadline must not disable mid-flight shedding for the
        # rest of the group — the old ``all(...)`` guard did exactly that
        # — so it may itself be truncated at the group's latest deadline;
        # that is the documented cost of being batched with
        # deadline-bearing work.
        with_deadline = [p.deadline for p in group if p.deadline is not None]
        deadline = max(with_deadline, key=lambda d: d.cutoff_ns()) \
            if with_deadline else None
        try:
            out = self.engine.generate(tokens, max_new_tokens=maxn,
                                       stop_token=group[0].stop_token,
                                       deadline=deadline,
                                       sampling=group[0].sampling)
        except Exception as e:  # noqa: BLE001 - fail every member, keep serving
            for p in group:
                if not p.future.done():
                    p.future.set_exception(e)
            return
        self.stats["batches"] += 1
        self.stats["batched_rows"] += tokens.shape[0]
        row = 0
        for p in group:
            res = out[row:row + p.rows, :min(p.max_new_tokens, out.shape[1])]
            row += p.rows
            if p.stop_token is not None:
                # Re-apply the request's own stop rule: solo generation ends
                # at the first step where every row of THIS request emits
                # the stop token; merged batches run longer, so trim back to
                # keep responses independent of what they were batched with.
                hits = (res == p.stop_token).all(axis=0)
                if hits.any():
                    res = res[:, :int(np.argmax(hits))]
            if not p.future.done():  # racing cancel() must not kill us
                p.future.set_result(np.ascontiguousarray(res))

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout=5.0)
        with self._cond:
            while self._queue:
                p = self._queue.popleft()
                p.future.set_exception(ShedError("batcher closed"))

    def mean_batch_rows(self) -> float:
        b = self.stats["batches"]
        return self.stats["batched_rows"] / b if b else 0.0

    def collect_stats(self) -> Dict[str, float]:
        """Complete snapshot: every counter (all keys pre-initialized at
        construction) plus live queue depth."""
        out: Dict[str, float] = dict(self.stats)
        out["queued_requests"] = len(self._queue)
        return out


# --------------------------------------------------------------------------
# Paged scheduling (block-pooled KV cache, mixed-length batching)
# --------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)   # identity semantics: field-wise eq would
class _PagedReq:                   # compare [B, T] arrays of mixed shapes
    """One in-flight request: rows share a prompt and advance in lockstep."""

    tokens: np.ndarray                  # [B, T] prompt
    max_new_tokens: int
    stop_token: Optional[int]
    deadline: Optional[Any]
    future: _cf.Future
    rid: int
    on_token: Optional[Callable[[int, np.ndarray], None]] = None
    sampling: SamplingParams = GREEDY
    # n>1 parallel sampling: the request prefills as ONE row (one prompt
    # allocation, prefix-cache eligible) and _fork() expands it to
    # fork_n candidate rows sharing the prompt's blocks at the moment
    # the first generated token is sampled
    fork_n: int = 1
    forked: bool = False
    # per-candidate stop mask for forked requests (None otherwise): a
    # candidate that samples stop_token freezes to stop-token padding
    # while its siblings keep generating — clients trim each row at its
    # first stop token
    done: Optional[np.ndarray] = None
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    # SLO-aware scheduling: priority class (higher preempts strictly
    # lower) and per-request latency targets in seconds (0 = no target)
    priority: int = 0
    ttft_slo_s: float = 0.0
    tpot_slo_s: float = 0.0
    first_emit_at: Optional[float] = None   # observed TTFT/TPOT inputs
    last_emit_at: Optional[float] = None
    cancelled: bool = False     # caller gone (connection died): stop paying
    # runtime state (set at admission)
    tables: Optional[np.ndarray] = None     # [B, M] int32 block tables
    slots: List[int] = dataclasses.field(default_factory=list)
    next_tok: Optional[np.ndarray] = None   # [B] pending (unemitted) tokens
    out: List[np.ndarray] = dataclasses.field(default_factory=list)
    pos_next: int = 0                       # absolute position of next write
    # [B, T + max_new + 1] committed-token history, maintained on emit so
    # the speculative drafter never rebuilds it (None when spec is off)
    hist: Optional[np.ndarray] = None

    @property
    def rows(self) -> int:
        return self.tokens.shape[0]

    @property
    def slots_needed(self) -> int:
        """Batch slots the request will occupy at its widest: a pending
        fork prefills as one row but must reserve ``fork_n`` slots up
        front so the expansion never deadlocks on a full batch."""
        return max(self.rows, self.fork_n)

    @property
    def seq_len(self) -> int:
        return self.tokens.shape[1]

    @property
    def prefilling(self) -> bool:
        """Prompt tokens remain to be written into the paged cache."""
        return self.pos_next < self.seq_len

    def expired(self) -> bool:
        # cancelled rides the expiry path: every shed sweep that reclaims
        # an expired request's resources (queued, active mid-prefill or
        # mid-decode, swapped out) reclaims a cancelled one's too
        return self.cancelled or (
            self.deadline is not None and self.deadline.expired())

    def emit(self, tok: np.ndarray) -> None:
        now = time.monotonic()
        if self.first_emit_at is None:
            self.first_emit_at = now
        self.last_emit_at = now
        self.out.append(tok)
        if self.hist is not None:
            self.hist[:, self.seq_len + len(self.out) - 1] = tok
        if self.on_token is not None:
            try:
                self.on_token(len(self.out) - 1, tok)
            except Exception:  # noqa: BLE001 - a hook must never be able
                # to desync scheduler state (skipped pos_next/next_tok
                # updates would re-feed and duplicate this token)
                _log.exception("on_token callback raised; ignoring")


class PagedBatcher:
    """Mixed-length continuous batching over the paged KV cache.

    Every admitted request owns fixed-stride blocks in one shared pool
    (serving/kv_cache.py), so batch assembly is just "which rows are
    live": one jitted :meth:`~repro.models.transformer.DecoderLM.paged_step`
    advances all active rows regardless of their prompt lengths or
    positions, and new requests slot in *between decode steps* of
    in-flight ones — no shape-compatible grouping, no whole-group
    re-prefill.

    Prefill never blocks the batch: admission only installs a
    prefill-in-progress row into free slots, and the scheduler runs
    *fused* steps — one ``paged_step`` call of chunk width advances every
    decode row by 1 token AND every prefilling row by up to
    ``prefill_chunk`` prompt tokens (per-row ``last_idx`` carries the
    valid counts), budgeted by ``ServeConfig.max_step_tokens``.  p50
    inter-token latency of in-flight decodes is therefore O(1 step) under
    long-prompt admission instead of O(prompt length).
    ``fused_prefill=False`` restores the blocking chunked-prefill loop
    (the benchmark baseline).

    With ``ServeConfig.prefix_cache`` on (the default), admission matches
    each prompt block-by-block against the content-hash index of
    already-resident prefixes: matched blocks are shared into the new
    request's table (refcounted, never copied), prefill starts at the
    cache-hit boundary, and a write that would touch a still-shared
    block copy-on-writes a private replacement first.  Finished
    requests' indexed blocks stay resident in an LRU until the pool
    needs them back, so a hot system prompt's KV survives between
    requests.  ``stats["prefix_hits"]`` / ``stats["prefix_tokens_reused"]``
    / ``stats["cow_copies"]`` expose the cache's behavior.

    Sampling rides the same steps: a request whose
    :class:`~repro.serving.sampling.SamplingParams` has temperature > 0
    draws each token through the seeded folded-key sampler (greedy
    requests keep the historical argmax bit-for-bit), speculative
    verification switches from exact-match to rejection sampling, and
    ``submit(n=...)`` forks a prefilled prompt into n candidate rows
    that share its KV blocks and diverge by copy-on-write
    (:meth:`_fork`).  ``stats["sampled_requests"]`` / ``stats["forks"]``
    / ``stats["spec_resamples"]`` expose the tier's behavior.

    Shedding happens at three points: on submit (queue full / already
    expired), at admission (expired in queue), and before each step
    (expired requests — including mid-prefill — are evicted, their blocks
    returned to the pool, and their prefix delivered — same contract as
    the dense path).  Requests the pool can never hold (more rows than
    ``max_batch`` or prompts longer than the table) fall back to the
    dense engine inline.

    With ``ServeConfig.swap`` on (the default), pool pressure preempts
    instead of shedding: when a queued request cannot be admitted (or a
    copy-on-write cannot get a block), the scheduler picks victims among
    strictly-lower-priority active requests — lowest priority first,
    most blocks first, always whole requests (no partial swaps) — and
    pages their KV blocks to host memory (:meth:`PagedKVCache.swap_out`).
    A preempted request resumes token-identically once blocks and slots
    free up (highest priority first), and one that exceeds its deadline
    while paged out is shed with both its host image and (already
    returned) device blocks reclaimed.  Per-request TTFT/TPOT SLO
    targets feed a small controller that nudges the live
    ``max_step_tokens`` prefill/decode split toward whichever target the
    recent window violates more.  ``stats["preemptions"]`` /
    ``stats["swapped_blocks"]`` / ``stats["swap_ins"]`` /
    ``stats["slo_violations"]`` expose the tier's behavior; every stats
    key is pre-initialized at construction so dashboards can rely on
    presence before the first increment.

    ``stats["step_calls"]`` counts jitted step calls of every kind and
    ``stats["step_host_ns"]`` the worker's host time in admission, batch
    assembly, dispatch and token selection: the time the device waits
    for the host.
    """

    def __init__(self, engine: Engine, *, max_batch: Optional[int] = None,
                 max_queue: int = 64):
        if not engine.supports_paged:
            raise ValueError(
                f"{engine.cfg.name}: model family has no paged-KV support; "
                f"use ContinuousBatcher")
        self.engine = engine
        cfg, sc = engine.cfg, engine.serve
        self.max_batch = max_batch or sc.max_batch
        self.max_queue = max_queue
        self.prefill_chunk = max(1, sc.prefill_chunk)
        self.fused = bool(sc.fused_prefill)
        self.max_step_tokens = max(0, int(sc.max_step_tokens))
        self.prefix_enabled = bool(sc.prefix_cache)
        self.spec_len = max(0, int(sc.spec_len))
        self.spec = bool(sc.spec_decode) and self.spec_len > 0
        self.spec_ngram = max(1, int(sc.spec_ngram))
        self.swap = bool(sc.swap)
        self.default_priority = int(sc.default_priority)
        self.ttft_slo_s = max(0.0, float(sc.ttft_slo_ms)) / 1e3
        self.tpot_slo_s = max(0.0, float(sc.tpot_slo_ms)) / 1e3
        self.slo_adjust_every = max(1, int(sc.slo_adjust_every))
        full_layers, window_layers = engine.model.paged_layers()
        # window layers keep only each row's KV ring (kv_cache.py): a
        # prefix hit would start a row with an empty ring, and the
        # blocking prefill loop steps rows outside their batch slots
        self.prefix_refused: Optional[str] = None
        if window_layers and self.prefix_enabled:
            self.prefix_refused = (
                f"{cfg.name}: prefix-cache hits are refused: its window "
                "layers keep only each row's KV ring, which a hit would "
                "leave without the prefix's last window")
            _log.warning(self.prefix_refused)
            self.prefix_enabled = False
        if window_layers and not self.fused:
            raise ValueError(f"{cfg.name}: window layers need the fused "
                             "prefill steps (fused_prefill=True)")
        bs = aligned_block_size(sc.block_size, cfg.head_dim, cfg.dtype)
        ring_bs, ring = ring_geometry(
            cfg.window if window_layers else 0, bs,
            max(self.prefill_chunk, sc.spec_len + 1))
        self.cache = PagedKVCache(
            num_layers=full_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, cache_len=sc.cache_len,
            block_size=sc.block_size, num_blocks=sc.num_blocks,
            max_concurrent=self.max_batch, dtype=cfg.dtype,
            prefix_cache=self.prefix_enabled,
            prefix_lru_blocks=sc.prefix_lru_blocks,
            ring_blocks=ring, ring_layers=window_layers,
            ring_block_size=ring_bs)
        self.cache.pool = engine.model.init_paged_pool(
            self.cache.layout.num_blocks, self.cache.block_size,
            self.cache.ring_rows, ring_bs)
        self._step_fn = engine.paged_step_fn()
        self._verify_fn = engine.paged_verify_fn() if self.spec else None
        # copy-on-write: duplicate one block of the full layers' pool
        # (donated, so in place); rings are private to their batch row
        self._copy_block = jax.jit(
            lambda pool, src, dst: dict(
                pool, k=pool["k"].at[:, dst].set(pool["k"][:, src]),
                v=pool["v"].at[:, dst].set(pool["v"][:, src])),
            donate_argnums=(0,))
        self._queue: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False  # guarded by _cond
        self._active: List[_PagedReq] = []
        self._slots: List[Optional[Tuple[_PagedReq, int]]] = \
            [None] * self.max_batch
        self._rids = itertools.count(1)   # next() on a count is atomic
        self._preempted: List[_PagedReq] = []
        self._ttft_obs: collections.deque = collections.deque(maxlen=128)
        self._tpot_obs: collections.deque = collections.deque(maxlen=128)
        self._steps_since_adjust = 0
        # ceiling for the SLO controller: one full chunk for every row
        self._step_budget_cap = max(self.max_batch * self.prefill_chunk,
                                    self.max_step_tokens)
        # every counter the batcher will ever report, initialized up
        # front: dashboards and tests can rely on key presence before
        # the first increment (keys used to appear on first touch)
        self.stats = {"requests": 0, "rows": 0, "shed": 0, "decode_steps": 0,
                      "batched_rows": 0, "prefill_chunks": 0,
                      "mixed_steps": 0, "admitted_in_flight": 0,
                      "dense_fallbacks": 0, "worker_errors": 0,
                      "prefix_hits": 0, "prefix_tokens_reused": 0,
                      "cow_copies": 0, "spec_steps": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "preemptions": 0, "swapped_blocks": 0, "swap_ins": 0,
                      "slo_violations": 0, "slo_adjustments": 0,
                      "cancelled": 0, "forks": 0, "spec_resamples": 0,
                      "sampled_requests": 0, "step_calls": 0,
                      "step_host_ns": 0, "kv_full_blocks": 0,
                      "kv_window_blocks": 0}
        self._worker_error_logged = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="serve-paged-batcher")
        self._worker.start()

    # -- admission ----------------------------------------------------------
    def submit(self, tokens: np.ndarray, *,
               max_new_tokens: Optional[int] = None,
               stop_token: Optional[int] = None,
               deadline=None, on_token=None,
               priority: Optional[int] = None,
               ttft_slo_ms: Optional[float] = None,
               tpot_slo_ms: Optional[float] = None,
               params: Optional[GenerationParams] = None,
               sampling: Optional[SamplingParams] = None,
               n: int = 1) -> _cf.Future:
        """Queue a [B, T] (or [T]) prompt; resolves to [B, new] int32.

        ``on_token(index, tok)`` is invoked from the worker thread as each
        token is emitted (latency instrumentation / streaming hooks).
        ``priority`` (higher wins; default ``ServeConfig.default_priority``)
        and the ``ttft_slo_ms``/``tpot_slo_ms`` latency targets (0 = no
        target; defaults from ServeConfig) drive the SLO-aware tier.

        ``params`` (a validated :class:`~repro.serving.sampling.\
GenerationParams`) supplies every per-request knob at once — the RPC
        service hands it straight through; explicit keyword arguments
        above win over fields it leaves ``None``.  ``sampling`` overrides
        the ServeConfig-default :class:`SamplingParams` (temperature 0 =
        greedy, the historical behavior).

        ``n > 1`` requests **parallel sampling**: a single-row prompt is
        prefilled ONCE, then forked into ``n`` candidate rows that
        ``share()`` the prompt's KV blocks through the refcounted
        allocator and diverge via copy-on-write from the first sampled
        token — the future resolves to [n, new] int32.  Each candidate
        stops independently: a row that samples ``stop_token`` freezes
        to stop-token padding while its siblings continue, so clients
        trim each row at its first stop token.

        Scheduling invariants the tests enforce:

        * **Determinism across contention.**  The emitted token sequence
          depends only on the prompt and the model — never on batching,
          chunked/fused prefill, speculative decode, or preempt/resume
          (a swapped request restores bit-identical KV state).
        * **Priority preempts strictly lower.**  A queued request only
          ever claims blocks by paging out active victims of strictly
          lower priority (lowest first, most blocks first, whole
          requests only); equal-priority traffic is FIFO with
          skip-ahead and is never preempted by its peers at admission.
        * **Deadlines always resolve.**  Every future resolves: with
          the generated prefix at the deadline, or a :class:`ShedError`
          (submit, queue, mid-flight, or while swapped out — the latter
          reclaims host and device resources alike).
        * **No capacity leaks.**  Whatever path retires a request
          (finish, shed, error, preempt-then-shed), every block
          reference it held is released.
        """
        rid = next(self._rids)
        with span("sched.submit", rid=rid):
            if params is not None:
                params.validate()
                max_new_tokens = params.max_new_tokens
                stop_token = params.stop_token
                priority = params.priority
                ttft_slo_ms = params.ttft_slo_ms
                tpot_slo_ms = params.tpot_slo_ms
                sampling = params.sampling(self.engine.serve)
                n = params.n
            tokens = np.atleast_2d(np.asarray(tokens, dtype=np.int32))
            sp = _config_sampling(self.engine.serve) if sampling is None \
                else sampling
            n = max(1, int(n))
            if n > 1 and tokens.shape[0] != 1:
                raise ValueError(
                    f"n={n} parallel sampling needs a single-row prompt, "
                    f"got batch {tokens.shape[0]}")
            maxn = self.engine.serve.max_new_tokens if max_new_tokens is None \
                else max_new_tokens  # explicit 0 = prefill-only
            pr = self.default_priority if priority is None else int(priority)
            ttft = self.ttft_slo_s if ttft_slo_ms is None \
                else max(0.0, float(ttft_slo_ms)) / 1e3
            tpot = self.tpot_slo_s if tpot_slo_ms is None \
                else max(0.0, float(tpot_slo_ms)) / 1e3
            with self._cond:
                p = _PagedReq(tokens, maxn, stop_token, deadline, _cf.Future(),
                              rid, on_token, priority=pr,
                              ttft_slo_s=ttft, tpot_slo_s=tpot,
                              sampling=sp, fork_n=n)
                if p.seq_len == 0:
                    # reject at the door: an installed 0-token request has
                    # no prefill to run and no next_tok to feed — it would
                    # poison the SHARED step and fail every in-flight
                    # request
                    self.stats["shed"] += 1
                    p.future.set_exception(ShedError("empty prompt"))
                    return p.future
                if self._closed:
                    self.stats["shed"] += 1
                    p.future.set_exception(ShedError("batcher closed"))
                    return p.future
                if p.expired():
                    self.stats["shed"] += 1
                    p.future.set_exception(
                        ShedError("deadline expired before admission"))
                    return p.future
                if len(self._queue) >= self.max_queue:
                    self.stats["shed"] += 1
                    p.future.set_exception(ShedError("admission queue full"))
                    return p.future
                self._queue.append(p)
                self.stats["requests"] += 1
                self.stats["rows"] += p.rows
                if not sp.greedy:
                    self.stats["sampled_requests"] += 1
                self._cond.notify()
            return p.future

    def generate(self, tokens: np.ndarray, **kw) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(tokens, **kw).result()

    def cancel(self, fut: _cf.Future) -> bool:
        """Mark the request owning ``fut`` cancelled (caller's connection
        died): the scheduler's next sweep reclaims whatever it holds —
        queue slot, active rows' KV blocks (even mid-prefill), or a
        swapped-out host image — through the same paths that reclaim an
        expired deadline.  Returns True if the request was found.
        """
        with self._cond:
            for p in (*self._queue, *self._active, *self._preempted):
                if p.future is fut:
                    p.cancelled = True
                    self.stats["cancelled"] += 1
                    self._cond.notify_all()
                    return True
        return False

    # -- worker -------------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._active \
                        and not self._preempted and not self._closed:
                    self._cond.wait()
                if self._closed and not self._queue and not self._active:
                    for req in list(self._preempted):
                        self._retire(req, exc=ShedError("batcher closed"))
                    return
            try:
                self._admit()
                if self._active:
                    self._step()
            except Exception:  # noqa: BLE001 - the worker must survive
                # per-request failure paths resolve futures themselves;
                # anything escaping here must not kill the only worker
                # thread — but a silent infinite retry is a wedged server
                # nobody can see, so count it and log the first.
                self.stats["worker_errors"] += 1
                if not self._worker_error_logged:
                    self._worker_error_logged = True
                    _log.exception(
                        "PagedBatcher worker step raised; continuing "
                        "(further escapes counted in "
                        "stats['worker_errors'])")
                continue

    def _take_admittable(self) -> Tuple[Optional[_PagedReq],
                                        Optional[_PagedReq]]:
        """(request to prefill, request to run dense) — at most one each.

        FIFO with skip-ahead: a queued request that fits the free slots
        and blocks right now is taken even if an earlier, larger one is
        still waiting (the earlier one keeps its queue position).
        """
        with self._cond:
            if not self._queue:   # the common mid-generation case: don't
                return None, None  # pay the LRU scan below per decode step
            free_slots = self.max_batch - sum(
                1 for s in self._slots if s is not None)
            # reclaimable walks the prefix LRU (O(idle cached blocks));
            # nothing in this loop allocates or evicts, so hoist the scan
            # out of the per-queued-request iteration
            free_budget = self.cache.num_free_blocks + self.cache.reclaimable
            for p in list(self._queue):
                if p.expired():
                    self._queue.remove(p)
                    self.stats["shed"] += 1
                    p.future.set_exception(
                        ShedError("deadline expired in queue"))
                    continue
                if p.slots_needed > self.max_batch \
                        or p.seq_len + max(p.max_new_tokens, 0) \
                        > self.cache.layout.tokens:
                    # doesn't fit the paged budget (too many rows, or the
                    # prompt + generation would overrun the block table):
                    # the dense path serves it with its own semantics
                    self._queue.remove(p)
                    return None, p
                need = self._blocks_need(p)
                if need > self.cache.allocator.capacity:
                    # can NEVER fit this pool: shed now, don't wedge the
                    # queue behind an unsatisfiable request
                    self._queue.remove(p)
                    self.stats["shed"] += 1
                    p.future.set_exception(ShedError(
                        f"request needs {need} KV blocks, pool capacity "
                        f"is {self.cache.allocator.capacity}"))
                    continue
                if p.slots_needed <= free_slots and need <= free_budget:
                    # free_budget counts idle prefix-cache blocks: a
                    # CacheOOM evicts them before shedding, and matched
                    # blocks are shared rather than consumed, so this
                    # bound is conservative
                    self._queue.remove(p)
                    return p, None
            return None, None

    def _blocks_need(self, p: _PagedReq) -> int:
        """Worst-case device blocks ``p`` needs over its lifetime.

        Plain requests: every row pays its full prompt + generation
        footprint.  Fork requests (``fork_n > 1``): one prompt footprint
        plus, per extra candidate, the private tail past the shared
        prompt blocks and one block for the copy-on-write of the shared
        boundary block the candidate's first divergent write touches.
        """
        per_row = self.cache.blocks_needed(
            p.seq_len + max(p.max_new_tokens, 0))
        if p.fork_n > 1:
            shared = min(-(-p.seq_len // self.cache.block_size), per_row)
            return per_row + (p.fork_n - 1) * (per_row - shared + 1)
        return per_row * p.rows

    def _admit(self) -> None:
        if self.swap:
            self._sweep_preempted()
        while True:
            req, dense = self._take_admittable()
            if dense is not None:
                with span("sched.dense", rid=dense.rid):
                    self._run_dense(dense)
                continue
            if req is None and self.swap:
                req = self._admit_by_preemption()
            if req is None:
                return
            if self._active:
                self.stats["admitted_in_flight"] += 1
            try:
                with span("sched.admit", self.stats, "step_host_ns",
                          rid=req.rid):
                    self._install(req)
                if not self.fused:
                    self._prefill_blocking(req)
            except Exception as e:  # noqa: BLE001 - fail THIS request only
                self._retire(req, exc=e)

    # -- preemption / swap tier ---------------------------------------------
    def _free_slots(self) -> int:
        return self.max_batch - sum(1 for s in self._slots if s is not None)

    def _free_budget(self) -> int:
        """Blocks an allocation could get right now (free + evictable)."""
        return self.cache.num_free_blocks + self.cache.reclaimable

    def _blocks_held(self, req: _PagedReq) -> int:
        """Block references ``req`` holds — the optimistic swap-out gain
        (a block another live request shares frees a reference, not a
        block; the execute loop re-verifies against real headroom)."""
        return sum(len(self.cache.allocator.blocks_of((req.rid, r)))
                   for r in range(req.rows))

    def _sweep_preempted(self) -> None:
        """Shed expired paged-out requests; resume the rest that fit now,
        highest priority first (FIFO among equals)."""
        for req in list(self._preempted):
            if req.expired():
                self._retire(req, exc=ShedError(
                    "deadline expired while swapped out"))
        for req in sorted(self._preempted,
                          key=lambda r: (-r.priority, r.enqueued_at)):
            self._try_resume(req)

    def _try_resume(self, req: _PagedReq) -> bool:
        """Swap a preempted request back in if slots and blocks allow.

        All-or-nothing across rows: if a later row's swap-in raises
        (allocation raced away), the rows already restored are swapped
        back out — content makes the round trip unchanged — and the
        request stays parked.
        """
        if req.slots_needed > self._free_slots():
            return False
        need = sum(self.cache.swapped_blocks((req.rid, r))
                   for r in range(req.rows))
        if need > self._free_budget():
            return False
        free = [i for i in range(self.max_batch)
                if self._slots[i] is None][:req.slots_needed]
        tabs: List[np.ndarray] = []
        try:
            for r in range(req.rows):
                # a row's KV ring goes to the batch row it resumes in
                tabs.append(self.cache.swap_in((req.rid, r),
                                               ring_row=free[r]))
        except CacheOOM:
            for r in range(len(tabs)):
                self.cache.swap_out((req.rid, r), ring_row=free[r])
            return False
        req.tables = np.stack(tabs)
        for i in free:
            self._slots[i] = (req, len(req.slots))
            req.slots.append(i)
        self._preempted.remove(req)
        self._active.append(req)
        self.stats["swap_ins"] += 1
        return True

    def _preempt(self, req: _PagedReq) -> None:
        """Page an active request's KV out to host and park it."""
        n = 0
        for r in range(req.rows):
            n += self.cache.swap_out((req.rid, r), ring_row=req.slots[r])
        for s in req.slots:
            self._slots[s] = None
        req.slots = []
        req.tables = None
        self._active.remove(req)
        self._preempted.append(req)
        self.stats["preemptions"] += 1
        self.stats["swapped_blocks"] += n

    def _preempt_candidate(self) -> Optional[Tuple[_PagedReq, int]]:
        """Highest-priority queued request the paged path could serve
        (FIFO among equals); returns (request, blocks needed)."""
        best: Optional[Tuple[_PagedReq, int]] = None
        with self._cond:
            for p in self._queue:
                if p.expired() or p.slots_needed > self.max_batch:
                    continue
                try:
                    need = self._blocks_need(p)
                except ValueError:
                    continue   # dense-fallback territory
                if need > self.cache.allocator.capacity:
                    continue   # unsatisfiable; _take_admittable sheds it
                if best is None or p.priority > best[0].priority:
                    best = (p, need)
        return best

    def _admit_by_preemption(self) -> Optional[_PagedReq]:
        """Make room for the best queued request by paging victims out.

        Victims are strictly-lower-priority actives, lowest priority
        first and most blocks first (fewest victims for the most relief),
        always swapped WHOLE — a partially-resident request would leave
        the scheduler with rows it can neither step nor cheaply restore.
        Returns the dequeued request once coverage is real, or None.
        """
        cand = self._preempt_candidate()
        if cand is None:
            return None
        p, need = cand
        lower = sorted((a for a in self._active if a.priority < p.priority),
                       key=lambda a: (a.priority, -self._blocks_held(a)))
        if not lower:
            return None
        if need > self._free_budget() + sum(map(self._blocks_held, lower)) \
                or p.rows > self._free_slots() \
                + sum(len(v.slots) for v in lower):
            return None   # even paging every lower victim out can't cover
        it = iter(lower)
        while self._free_budget() < need or self._free_slots() < p.rows:
            v = next(it, None)
            if v is None:
                # prefix sharing made the optimistic bound wrong; the
                # victims already paged out simply resume on a later
                # sweep — no state to unwind
                return None
            self._preempt(v)
        with self._cond:
            if p not in self._queue:
                return None   # shed behind our back (deadline race)
            self._queue.remove(p)
        return p

    def _cow_or_relieve(self, req: _PagedReq, adv: int) -> bool:
        """:meth:`_cow_writes` with pool-pressure relief.

        On CacheOOM (swap enabled): page out the lowest-priority
        strictly-lower victim and retry; with no such victim,
        self-preempt — the request keeps its generated work on host and
        resumes later — unless it is the only active request, where
        parking it could never free anything.  Re-running the COW scan
        after relief is idempotent: blocks already privatized probe as
        exclusively owned.  Returns False when ``req`` left the batch.
        """
        while True:
            try:
                self._cow_writes(req, adv)
                return True
            except CacheOOM as e:
                if not self.swap:
                    self._retire(req, exc=e)
                    return False
                lower = sorted(
                    (a for a in self._active
                     if a is not req and a.priority < req.priority),
                    key=lambda a: (a.priority, -self._blocks_held(a)))
                if lower:
                    self._preempt(lower[0])
                    continue
                if len(self._active) > 1:
                    self._preempt(req)
                    return False
                self._retire(req, exc=e)
                return False

    def _run_dense(self, p: _PagedReq) -> None:
        """Oversized request: dense engine inline (rare escape hatch)."""
        self.stats["dense_fallbacks"] += 1
        try:
            toks = p.tokens if p.fork_n <= 1 \
                else np.repeat(p.tokens, p.fork_n, axis=0)
            out = self.engine.generate(toks,
                                       max_new_tokens=p.max_new_tokens,
                                       stop_token=p.stop_token,
                                       deadline=p.deadline,
                                       sampling=p.sampling)
        except Exception as e:  # noqa: BLE001
            if not p.future.done():
                p.future.set_exception(e)
            return
        if not p.future.done():
            p.future.set_result(out)

    # -- admission install (fused path: no device work) ---------------------
    def _install(self, req: _PagedReq) -> None:
        """Give the request blocks + batch slots; prefill happens in the
        scheduler's fused steps, never as a blocking loop here.

        With the prefix cache on, each row's prompt is first matched
        block-by-block against already-resident prefixes: matched blocks
        are shared (a refcount, not a copy) and ``pos_next`` starts at
        the cache-hit boundary, so their prefill is skipped entirely.
        """
        rows, t = req.rows, req.seq_len
        # admission guaranteed t + max_new <= layout.tokens, so every
        # position this request will ever write is covered by its table
        total = t + req.max_new_tokens
        limit = None
        row_keys: List[Optional[List[bytes]]] = [None] * rows
        if self.prefix_enabled and rows > 1:
            # lockstep rows share one pos_next: cap every row at the
            # weakest row's match so no row re-writes shared history
            # (keys hashed once here, reused by allocate_prefix below)
            row_keys = [block_keys(req.tokens[r], self.cache.block_size)
                        for r in range(rows)]
            limit = min(len(self.cache.prefix.lookup(k)) for k in row_keys)
        tabs, matched = [], []
        for r in range(rows):
            if self.prefix_enabled:
                row_tab, m_tok, _ = self.cache.allocate_prefix(
                    (req.rid, r), total, req.tokens[r], limit=limit,
                    keys=row_keys[r])
            else:
                row_tab, m_tok = self.cache.allocate((req.rid, r), total), 0
            tabs.append(row_tab)
            matched.append(m_tok)
        req.tables = np.stack(tabs)
        req.pos_next = min(matched)
        if self.spec:
            # one growing history buffer per row (prompt now, generated
            # tokens appended on emit): the drafter reads a view instead
            # of re-concatenating the prompt + every emitted token
            req.hist = np.zeros((rows, t + max(req.max_new_tokens, 0) + 1),
                                np.int32)
            req.hist[:, :t] = req.tokens
        if req.pos_next:
            self.stats["prefix_hits"] += rows
            self.stats["prefix_tokens_reused"] += req.pos_next * rows
        for i in range(self.max_batch):
            if len(req.slots) == req.slots_needed:
                break
            if self._slots[i] is None:
                self._slots[i] = (req, len(req.slots))
                req.slots.append(i)
        self._active.append(req)

    def _cow_writes(self, req: _PagedReq, adv: int) -> None:
        """Copy-on-write any SHARED block the coming write range
        ``[pos_next, pos_next + adv)`` touches: a write must never mutate
        a block other requests (or the prefix index) still read.  The
        organic case is the cache-hit boundary landing inside a
        fully-matched block (prompt length a multiple of the block
        size); the scan itself is one refcount probe per touched block.
        """
        if adv <= 0 or req.tables is None \
                or not (self.prefix_enabled or req.forked):
            return  # forks share blocks even with the prefix cache off
        for r in range(req.rows):
            for idx, src, dst in self.cache.ensure_private_range(
                    (req.rid, r), req.pos_next, adv):
                self.cache.pool = self._copy_block(
                    self.cache.pool, np.int32(src), np.int32(dst))
                req.tables[r, idx] = dst
                self.stats["cow_copies"] += 1

    def _register_prefix(self, req: _PagedReq) -> None:
        """Index the request's fully-written full prompt blocks, so later
        prompts (and concurrent identical ones) can share them."""
        if self.prefix_enabled:
            for r in range(req.rows):
                self.cache.register_progress((req.rid, r), req.tokens[r],
                                             req.pos_next)

    # -- blocking chunked prefill (fused_prefill=False baseline) ------------
    def _prefill_blocking(self, req: _PagedReq) -> None:
        """Run every prompt chunk of a just-installed request to
        completion before returning — the scheduler the fused steps
        replace (kept as the benchmark baseline)."""
        rows, t = req.rows, req.seq_len
        c = self.prefill_chunk
        logits = None
        while req.pos_next < t:
            if req.pos_next and req.expired():
                # mid-prefill expiry: deliver the empty prefix (the dense
                # path's contract: prefill done, zero tokens generated)
                self._retire(req)
                return
            adv = min(c, t - req.pos_next)
            self._cow_writes(req, adv)   # may rewrite req.tables entries
            toks = np.zeros((rows, c), np.int32)
            toks[:, :adv] = req.tokens[:, req.pos_next:req.pos_next + adv]
            pos = np.broadcast_to(
                req.pos_next + np.minimum(np.arange(c, dtype=np.int32),
                                          adv - 1), (rows, c))
            last = np.full((rows,), adv - 1, np.int32)
            self.stats["step_calls"] += 1
            logits, self.cache.pool = self._step_fn(
                self.engine.params, jnp.asarray(toks), self.cache.pool,
                jnp.asarray(req.tables), jnp.asarray(pos),
                jnp.asarray(last))
            self.stats["prefill_chunks"] += 1
            req.pos_next += adv
            self._register_prefix(req)
        self._finish_prefill(req, np.asarray(logits))

    # -- prefill completion / n>1 fork --------------------------------------
    def _finish_prefill(self, req: _PagedReq, logits: np.ndarray) -> None:
        """Prompt fully written: fork (n>1), pick the first token, retire
        if the request is already done.

        ``logits`` holds one row per REAL row (a pending fork's single
        prefill row); the fork broadcasts it to every candidate — they
        share the prompt's distribution and diverge only through their
        per-candidate sample draws.
        """
        logits = np.asarray(logits)
        if req.max_new_tokens <= 0 or req.expired():
            self._retire(req)
            return
        if req.fork_n > 1 and not req.forked:
            try:
                self._fork(req)
            except CacheOOM as e:
                self._retire(req, exc=e)
                return
            logits = np.broadcast_to(logits, (req.rows,) + logits.shape[1:])
        req.next_tok = self._next_from(req, logits, 0)
        if req.done is not None:
            req.done |= req.next_tok == req.stop_token
            req.next_tok = np.where(req.done, req.stop_token,
                                    req.next_tok).astype(np.int32)
            if bool(req.done.all()):
                self._retire(req)

    def _fork(self, req: _PagedReq) -> None:
        """Expand a prefilled single-row request to ``fork_n`` candidate
        rows that share its prompt blocks (refcounts, not copies).

        Each extra candidate shares every block the prompt occupies —
        including a partially-filled boundary block, which the row's
        first divergent write copy-on-writes private — and allocates its
        generation tail fresh.  On CacheOOM the rows already forked are
        released so the request retires holding only its prefill row.
        """
        n = req.fork_n
        tabs = [req.tables[0]]
        try:
            for r in range(1, n):
                tabs.append(self.cache.fork((req.rid, 0), (req.rid, r),
                                            shared_tokens=req.seq_len))
        except CacheOOM:
            for rr in range(1, len(tabs)):
                self.cache.release((req.rid, rr))
            raise
        req.tables = np.stack(tabs)
        if self.cache.layout.ring_blocks:
            # each candidate's batch row takes the prompt's last window
            for r in range(1, n):
                self.cache.copy_ring(req.slots[0], req.slots[r])
        req.tokens = np.repeat(req.tokens, n, axis=0)
        if req.hist is not None:
            req.hist = np.repeat(req.hist, n, axis=0)
        if req.stop_token is not None:
            # per-candidate stop: rows finish independently (unlike the
            # lockstep multi-row prompt path)
            req.done = np.zeros(n, bool)
        req.forked = True
        self.stats["forks"] += n - 1

    def _next_from(self, req: _PagedReq, logits: np.ndarray,
                   index: int) -> np.ndarray:
        """Choose the token at output position ``index`` for every row.

        Greedy keeps the historical pure-numpy argmax; sampled requests
        draw through the folded-key schedule with candidate offset 0 —
        row r of a forked request IS candidate r, so siblings see
        distinct streams while the request's tokens stay independent of
        batch composition.
        """
        if req.sampling.greedy:
            return logits.argmax(-1).astype(np.int32)
        return sample_tokens(logits, req.sampling, index=index)

    # -- scheduling ---------------------------------------------------------
    def _table_width(self, max_ctx: int) -> int:
        """Block-table columns needed for ``max_ctx`` tokens, rounded up to
        a power of two (bounded set of jit shapes), capped at the layout.

        Short-context batches stop paying ``blocks_per_seq`` grid steps of
        ``pl.when`` skips in the kernels: the tables are sliced to this
        width before the call, so the block axis of the grid is
        ``ceil(max_ctx / bs)`` (rounded) instead of the full table.
        """
        need = max(1, -(-max_ctx // self.cache.block_size))
        w = 1
        while w < need:
            w <<= 1
        return min(w, self.cache.blocks_per_seq)

    def _call_step(self, fn, toks, tables, pos, last) -> np.ndarray:
        """Run one jitted step over the assembled batch arrays.

        Shared scaffolding of the mixed/decode/verify steps: a step that
        raises fails EVERY in-flight request (their blocks return to the
        pool) and re-raises so the worker loop's error accounting sees
        it.  Returns the logits as a host array: ``sched.dispatch`` ends
        once the call is enqueued, ``sched.fetch`` once the step has run
        and its logits are on the host."""
        self.stats["step_calls"] += 1
        try:
            with span("sched.dispatch", self.stats, "step_host_ns"):
                out, self.cache.pool = fn(
                    self.engine.params, jnp.asarray(toks), self.cache.pool,
                    jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(last))
        except Exception as e:  # noqa: BLE001 - fail every member, survive
            for req in list(self._active):
                self._retire(req, exc=e)
            raise
        with span("sched.fetch"):
            return np.asarray(out)

    def _step(self) -> None:
        """One device step: assemble the batch, run it, select tokens.

        Each phase is a host span on the profiler's clock
        (core/spans.py).  The worker's time in ``sched.assemble``,
        ``sched.dispatch``, ``sched.select`` and ``sched.admit`` is the
        host work the synchronous loop makes the device wait for; it
        adds up in ``stats["step_host_ns"]``."""
        with span("sched.assemble", self.stats, "step_host_ns"):
            batch = self._assemble()
        if batch is None:
            return
        fn, arrays, select = batch
        logits = self._call_step(fn, *arrays)
        with span("sched.select", self.stats, "step_host_ns"):
            select(logits)
            self._note_blocks()

    def _note_blocks(self) -> None:
        """Peak live blocks of each KV pool: the full layers' blocks in use
        (prefix-cached ones too), and the window layers' ring blocks that
        hold positions of a live row (at most R a row)."""
        lo = self.cache.layout
        used = self.cache.allocator.capacity - self.cache.num_free_blocks
        self.stats["kv_full_blocks"] = max(self.stats["kv_full_blocks"],
                                           used)
        if lo.ring_blocks:
            ring = sum(min(-(-req.pos_next // lo.ring_block_size),
                           lo.ring_blocks)
                       * req.rows for req in self._active)
            self.stats["kv_window_blocks"] = max(
                self.stats["kv_window_blocks"], ring)

    def _assemble(self) -> Optional[_Batch]:
        """The next step's jitted function, its batch arrays (toks,
        tables, pos, last) and the function that takes its logits; None
        when no row is left to step."""
        for req in list(self._active):   # evict expired before device work
            if req.expired():            # (incl. mid-prefill: blocks back)
                self._retire(req)
        self._steps_since_adjust += 1
        if self._steps_since_adjust >= self.slo_adjust_every:
            self._steps_since_adjust = 0
            self._slo_adjust()
        if not self._active:
            return None
        if any(req.prefilling for req in self._active):
            return self._mixed_batch()
        if self.spec:
            return self._spec_batch()
        return self._decode_batch()

    # -- fused mixed prefill/decode step ------------------------------------
    def _mixed_batch(self) -> Optional[_Batch]:
        """ONE jitted call: every decode row advances 1 token, every
        prefilling row advances up to ``prefill_chunk`` prompt tokens.

        All rows share the chunk width C; per-row ``last_idx`` carries how
        many of the C tokens are real (decode rows: 1).  The model routes
        padding writes to the null block, and the paged-prefill kernel's
        per-query position mask makes a padded decode row numerically
        identical to a width-1 decode — so interleaving costs no separate
        prefill pass and in-flight decodes never wait out a long prompt.
        """
        c = self.prefill_chunk
        b = self.max_batch
        prefilling = [r for r in self._active if r.prefilling]
        decoding = [r for r in self._active if not r.prefilling]
        # count REAL rows: a pending fork reserves fork_n slots but
        # prefills as one row
        n_decode = sum(r.rows for r in decoding)
        n_pf_rows = sum(r.rows for r in prefilling)
        if self.max_step_tokens > 0:
            # budget NEW tokens this step: decode rows cost 1 each, the
            # remainder is split across prefilling rows
            cap = max(1, (self.max_step_tokens - n_decode)
                      // max(n_pf_rows, 1))
            cap = min(cap, c)
        else:
            cap = c
        advances = {req.rid: min(cap, req.seq_len - req.pos_next)
                    for req in prefilling}
        # copy-on-write before the shared step: a row about to write into
        # a block the prefix cache (or another request) still reads gets
        # a private copy first.  A COW that cannot get a block even after
        # LRU eviction pages a victim (or itself) out to host — with
        # swap off it fails only ITS request, never the batch.
        for req in list(prefilling):
            if not self._cow_or_relieve(req, advances[req.rid]):
                prefilling.remove(req)
        for req in list(decoding):
            if not self._cow_or_relieve(req, 1):
                decoding.remove(req)
        # relief may have paged out victims from either list
        prefilling = [r for r in prefilling if r in self._active]
        decoding = [r for r in decoding if r in self._active]
        if not prefilling and not decoding:
            return None
        n_decode = sum(r.rows for r in decoding)
        max_ctx = max([req.pos_next + advances[req.rid]
                       for req in prefilling]
                      + [req.pos_next + 1 for req in decoding])
        m_used = self._table_width(max_ctx)
        toks = np.zeros((b, c), np.int32)
        tables = np.zeros((b, m_used), np.int32)  # null block for idle rows
        pos = np.zeros((b, c), np.int32)
        last = np.zeros((b,), np.int32)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            req, r = slot
            if r >= req.rows:
                continue   # slot reserved for a not-yet-forked candidate
            tables[i] = req.tables[r][:m_used]
            if req.prefilling:
                adv = advances[req.rid]
                toks[i, :adv] = req.tokens[r, req.pos_next:req.pos_next + adv]
                # padding repeats the last valid position (decode rows do
                # the same): keeps the kernel's per-row ctx tight so the
                # block-skip elides everything past the real advance
                pos[i] = req.pos_next + np.minimum(
                    np.arange(c, dtype=np.int32), adv - 1)
                last[i] = adv - 1
            else:
                toks[i, 0] = req.next_tok[r]
                pos[i] = req.pos_next     # pads masked via last_idx == 0

        def select(logits: np.ndarray) -> None:
            self.stats["mixed_steps"] += 1
            self.stats["prefill_chunks"] += len(prefilling)
            if decoding:
                self.stats["decode_steps"] += 1
                self.stats["batched_rows"] += n_decode
            for req in list(decoding):
                self._advance_decode(req, logits)
            for req in list(prefilling):
                req.pos_next += advances[req.rid]
                self._register_prefix(req)
                if not req.prefilling:
                    # prompt fully written: the chunk's last valid logits
                    # pick the first generated token (same as blocking
                    # prefill) — and a fork request expands to its
                    # candidate rows here, sharing the prompt blocks just
                    # written
                    self._finish_prefill(req, logits[req.slots[:req.rows]])
        return self._step_fn, (toks, tables, pos, last), select

    # -- speculative decode (draft-then-verify) -----------------------------
    def _draft(self, req: _PagedReq) -> Optional[np.ndarray]:
        """Per-row n-gram proposals for one decoding request.

        Returns a [rows, k] int32 array of drafted continuation tokens
        (lockstep rows are clamped to their shortest proposal so every
        row advances uniformly), or None when nothing useful can be
        drafted.  The draft budget never exceeds the tokens the request
        may still emit after its pending one — which also keeps every
        speculative write inside the block table the request was
        admitted with (allocation covers seq_len + max_new_tokens).
        """
        if req.fork_n > 1:
            # forked candidates diverge row-by-row; lockstep acceptance
            # would clamp every row to the weakest proposal, so forks
            # decode plainly (they still batch with drafting requests)
            return None
        budget = min(self.spec_len, req.max_new_tokens - len(req.out) - 1)
        if budget <= 0:
            return None
        hl = req.seq_len + len(req.out)
        req.hist[:, hl] = req.next_tok   # pending token caps the history
        rows = [ngram_propose(req.hist[r, :hl + 1], budget,
                              min_n=self.spec_ngram)
                for r in range(req.rows)]
        k = min(len(d) for d in rows)
        if k == 0:
            return None
        return np.stack([d[:k] for d in rows])

    def _spec_batch(self) -> Optional[_Batch]:
        """Draft-then-verify decode: ONE jitted verify step scores every
        row's pending token PLUS its drafted continuation (width
        ``spec_len + 1``, logits at every position), so an accepted
        prefix advances ``pos_next`` by several tokens in the step a
        plain decode would have spent on one.

        Rejected drafts need no undo: their K/V writes landed in
        positions past the committed context (copy-on-write already
        privatized any shared block in the write range), the position
        masks keep them unread, and the next step's writes overwrite
        them — rollback is "don't advance", exactly the prefix-cache
        ``register_progress`` discipline.  When no row drafts anything
        (non-repetitive traffic), the step falls through to the plain
        1-token decode so speculation never costs idle workloads.
        """
        drafts: Dict[int, np.ndarray] = {}
        for req in self._active:
            d = self._draft(req)
            if d is not None:
                drafts[req.rid] = d
        # drafting is host-side work: a deadline may expire between the
        # draft and the verify — shed here so an expired request's
        # blocks return to the pool without paying the device step
        for req in list(self._active):
            if req.expired():
                drafts.pop(req.rid, None)
                self._retire(req)
        if not self._active:
            return None
        if not drafts:
            return self._decode_batch()
        c = self.spec_len + 1
        b = self.max_batch
        for req in list(self._active):
            d = drafts.get(req.rid)
            if not self._cow_or_relieve(req, 1 + (d.shape[1] if d is not None
                                                  else 0)):
                drafts.pop(req.rid, None)
        if not self._active:
            return None
        max_ctx = max(
            req.pos_next + 1 + (drafts[req.rid].shape[1]
                                if req.rid in drafts else 0)
            for req in self._active)
        m_used = self._table_width(max_ctx)
        toks = np.zeros((b, c), np.int32)
        tables = np.zeros((b, m_used), np.int32)  # null block: idle rows
        pos = np.zeros((b, c), np.int32)
        last = np.zeros((b,), np.int32)
        n_rows = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            req, r = slot
            d = drafts.get(req.rid)
            k = 0 if d is None else d.shape[1]
            toks[i, 0] = req.next_tok[r]
            if k:
                toks[i, 1:1 + k] = d[r]
            # padding repeats the last valid position (same trick as the
            # mixed step): keeps each row's ctx tight for block skipping
            pos[i] = req.pos_next + np.minimum(
                np.arange(c, dtype=np.int32), k)
            last[i] = k
            tables[i] = req.tables[r][:m_used]
            n_rows += 1

        def select(logits: np.ndarray) -> None:
            self.stats["decode_steps"] += 1
            self.stats["spec_steps"] += 1
            self.stats["batched_rows"] += n_rows
            for req in list(self._active):
                self._advance_spec(req, logits, drafts.get(req.rid))
        return self._verify_fn, (toks, tables, pos, last), select

    def _advance_spec(self, req: _PagedReq, logits: np.ndarray,
                      draft: Optional[np.ndarray]) -> None:
        """Commit a verify step's result for one request.

        ``logits[slot, j]`` scores the vocabulary after the row consumed
        chunk tokens 0..j, so the emitted sequence below replays the
        sequential decode loop exactly: each iteration emits one token
        and applies the same max_new_tokens-then-stop-token checks as
        :meth:`_advance_decode` — speculative decode changes how many
        loop iterations one device step funds, never their semantics.

        Greedy requests keep exact-match acceptance (bit-identical to
        plain decode).  Sampled requests verify by rejection sampling
        (:func:`~repro.serving.sampling.rejection_sample`): draft token
        j is accepted with probability min(1, p_target/p_draft) — the
        n-gram drafter is deterministic, so p_draft is a point mass and
        the test reduces to a seeded uniform against p_target[draft_j] —
        and a rejected position resamples from the adjusted residual, so
        the output distribution is identical to non-speculative
        sampling (the realization may differ; at temperature 0 both
        paths collapse to argmax and stay bit-identical).
        """
        lx = logits[req.slots]                                  # [R, C, V]
        k = 0 if draft is None else draft.shape[1]
        if req.sampling.greedy:
            argm = lx.argmax(-1).astype(np.int32)               # [R, C]
            n_acc = 0   # lockstep rows: accept the prefix EVERY row accepts
            while n_acc < k \
                    and bool((argm[:, n_acc] == draft[:, n_acc]).all()):
                n_acc += 1
            seq = [argm[:, j] for j in range(n_acc + 1)]
        else:
            n_acc, seq = self._rejection_advance(req, lx, draft, k)
        if k:
            self.stats["spec_proposed"] += k * req.rows
            self.stats["spec_accepted"] += n_acc * req.rows
        req.emit(req.next_tok.copy())
        req.pos_next += 1
        for j, new in enumerate(seq):
            if len(req.out) >= req.max_new_tokens:
                self._retire(req)
                return
            if req.done is not None:
                req.done |= new == req.stop_token
                if bool(req.done.all()):
                    self._retire(req)         # stop token not emitted
                    return
                new = np.where(req.done, req.stop_token,
                               new).astype(np.int32)
            elif req.stop_token is not None \
                    and bool((new == req.stop_token).all()):
                self._retire(req)             # stop token not emitted
                return
            if j < n_acc:
                # verified: K/V for the token is already resident
                req.emit(new.copy())
                req.pos_next += 1
            else:
                req.next_tok = new.copy()     # first unverified token
                return

    def _rejection_advance(self, req: _PagedReq, lx: np.ndarray,
                           draft: Optional[np.ndarray],
                           k: int) -> Tuple[int, List[np.ndarray]]:
        """Rejection-sample a verify step's chunk for a sampled request.

        Returns ``(n_acc, seq)`` where ``seq`` holds the ``n_acc``
        accepted draft columns plus the one token that follows them —
        shaped exactly like the greedy path's output so
        :meth:`_advance_spec` replays both identically.  Lockstep rows
        commit the prefix every row accepts; a row that accepted further
        simply keeps its own draft token at the cut, and a row that
        rejected AT the cut takes its residual resample.  The non-draft
        case (k = 0) and the all-accepted bonus token use the SAME
        categorical draw plain decode would make at that output index,
        so a sampled request's tokens do not depend on whether its
        neighbors drafted.
        """
        rows = req.rows
        base = len(req.out) + 1   # output index of the first chunk token
        if k == 0:
            return 0, [sample_tokens(lx[:, 0], req.sampling, index=base)]
        probs = target_probs(lx[:, :k + 1], req.sampling)  # [R, k+1, V]
        u = spec_uniforms(req.sampling, base_index=base, rows=rows,
                          width=k + 1)
        acc = np.zeros(rows, np.int32)
        tok = np.zeros(rows, np.int32)
        rej = np.zeros(rows, bool)
        for r in range(rows):
            acc[r], tok[r], rej[r] = rejection_sample(
                probs[r], draft[r], u[r, :, 0], u[r, :, 1])
        n_acc = int(acc.min())
        if n_acc >= k:
            # every draft accepted everywhere: the bonus token is a plain
            # categorical draw from the position after the draft
            pend = sample_tokens(lx[:, k], req.sampling, index=base + k)
        else:
            pend = np.where(acc > n_acc, draft[:, n_acc], tok) \
                .astype(np.int32)
            self.stats["spec_resamples"] += int(np.sum((acc == n_acc) & rej))
        seq = [draft[:, j] for j in range(n_acc)] + [pend]
        return n_acc, seq

    # -- decode -------------------------------------------------------------
    def _decode_batch(self) -> Optional[_Batch]:
        b = self.max_batch
        for req in list(self._active):
            # decode writes rarely hit shared blocks (robustness backstop)
            self._cow_or_relieve(req, 1)
        if not self._active:
            return None
        max_ctx = max(req.pos_next + 1 for req in self._active)
        m_used = self._table_width(max_ctx)
        toks = np.zeros((b, 1), np.int32)
        tables = np.zeros((b, m_used), np.int32)  # null block for idle rows
        pos = np.zeros((b,), np.int32)
        n_rows = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            req, r = slot
            toks[i, 0] = req.next_tok[r]
            tables[i] = req.tables[r][:m_used]
            pos[i] = req.pos_next
            n_rows += 1

        def select(logits: np.ndarray) -> None:
            self.stats["decode_steps"] += 1
            self.stats["batched_rows"] += n_rows
            for req in list(self._active):
                self._advance_decode(req, logits)
        return (self._step_fn,
                (toks, tables, pos[:, None], np.zeros((b,), np.int32)),
                select)

    def _advance_decode(self, req: _PagedReq, logits: np.ndarray) -> None:
        """Emit the fed token, pick the next one, retire if done.

        Forked requests (``req.done`` set) stop per candidate: a row
        that picks ``stop_token`` freezes — its later picks are forced
        to the stop token, so the result pads with it — and the request
        retires once EVERY candidate has stopped (before emitting the
        all-stop column, so the lockstep trim in :meth:`_retire` never
        fires for forks).
        """
        req.emit(req.next_tok.copy())
        req.pos_next += 1
        new = self._next_from(req, logits[req.slots], len(req.out))
        if len(req.out) >= req.max_new_tokens:
            self._retire(req)
            return
        if req.done is not None:
            req.done |= new == req.stop_token
            if bool(req.done.all()):
                self._retire(req)             # stop token not emitted
                return
            new = np.where(req.done, req.stop_token, new).astype(np.int32)
        elif req.stop_token is not None \
                and bool((new == req.stop_token).all()):
            self._retire(req)                 # stop token not emitted
            return
        req.next_tok = new

    # -- SLO accounting -----------------------------------------------------
    def _note_slo(self, req: _PagedReq) -> None:
        """Record observed TTFT/TPOT against the request's targets.

        A request shed before its first token still yields a TTFT
        observation (its wait so far) — sheds under overload are exactly
        the signal the controller must see."""
        now = time.monotonic()
        if req.ttft_slo_s > 0:
            ttft = (req.first_emit_at - req.enqueued_at) \
                if req.first_emit_at is not None else now - req.enqueued_at
            self._ttft_obs.append((ttft, req.ttft_slo_s))
            if ttft > req.ttft_slo_s:
                self.stats["slo_violations"] += 1
        if req.tpot_slo_s > 0 and req.first_emit_at is not None \
                and len(req.out) > 1:
            tpot = (req.last_emit_at - req.first_emit_at) \
                / (len(req.out) - 1)
            self._tpot_obs.append((tpot, req.tpot_slo_s))
            if tpot > req.tpot_slo_s:
                self.stats["slo_violations"] += 1

    def _slo_adjust(self) -> None:
        """Feedback controller over the prefill/decode split.

        ``max_step_tokens`` is the one knob trading TTFT against TPOT: a
        bigger budget lets prefilling rows advance more prompt tokens per
        fused step (faster first token), a smaller one spends the step on
        decode rows (steadier inter-token latency).  Halve/double toward
        whichever target the recent window violates more, clamped to
        [max_batch + 1, max_batch * prefill_chunk]; the window resets
        after a move so stale observations can't double-trigger."""
        ttft, tpot = list(self._ttft_obs), list(self._tpot_obs)
        f_ttft = sum(1 for o, t in ttft if o > t) / len(ttft) if ttft else 0.0
        f_tpot = sum(1 for o, t in tpot if o > t) / len(tpot) if tpot else 0.0
        cur = self.max_step_tokens or self._step_budget_cap
        new = cur
        if f_tpot > f_ttft and f_tpot > 0.25:
            new = max(self.max_batch + 1, cur // 2)
        elif f_ttft > f_tpot and f_ttft > 0.25:
            new = min(self._step_budget_cap, cur * 2)
        if new != cur:
            self.max_step_tokens = new
            self.stats["slo_adjustments"] += 1
            self._ttft_obs.clear()
            self._tpot_obs.clear()

    # -- retirement ---------------------------------------------------------
    def _retire(self, req: _PagedReq, *,
                exc: Optional[BaseException] = None) -> None:
        """Free ALL of the request's resources (device blocks AND any
        host swap image) and resolve its future."""
        with span("sched.retire", rid=req.rid):
            for r in range(req.rows):
                self.cache.release((req.rid, r))
            for s in req.slots:
                self._slots[s] = None
            req.slots = []
            if req in self._active:
                self._active.remove(req)
            if req in self._preempted:
                self._preempted.remove(req)
            self._note_slo(req)
            if exc is not None:
                if not req.future.done():
                    req.future.set_exception(exc)
                return
            res = np.stack(req.out, axis=1) if req.out \
                else np.zeros((req.rows, 0), np.int32)
            if req.stop_token is not None and res.size:
                # same per-request trim as the dense batcher: responses are
                # independent of what they were batched with
                hits = (res == req.stop_token).all(axis=0)
                if hits.any():
                    res = res[:, :int(np.argmax(hits))]
            if not req.future.done():
                req.future.set_result(np.ascontiguousarray(res))

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout=5.0)
        with self._cond:
            while self._queue:
                p = self._queue.popleft()
                if not p.future.done():
                    p.future.set_exception(ShedError("batcher closed"))
            # normally drained by the worker's exit path; cover a worker
            # that died or timed out so no future is left dangling
            for p in self._preempted:
                if not p.future.done():
                    p.future.set_exception(ShedError("batcher closed"))
            self._preempted.clear()

    def mean_batch_rows(self) -> float:
        b = self.stats["decode_steps"]
        return self.stats["batched_rows"] / b if b else 0.0

    def collect_stats(self) -> Dict[str, float]:
        """Complete snapshot: every counter in :attr:`stats` (all keys
        pre-initialized at construction) plus live scheduler gauges."""
        out: Dict[str, float] = dict(self.stats)
        out["active_requests"] = len(self._active)
        out["queued_requests"] = len(self._queue)
        out["preempted_requests"] = len(self._preempted)
        out["free_blocks"] = self.cache.num_free_blocks
        out["max_step_tokens"] = self.max_step_tokens
        if self.cache.prefix is not None:
            out["prefix_indexed_blocks"] = len(self.cache.prefix)
            out["prefix_evictions"] = self.cache.prefix.evictions
        return out
