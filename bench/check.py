"""The comparison that decides ``correct``.

It compares what the timed path produced, at the timed sizes:

- every answer due in the window came (a minute late is late, not wrong);
- page ingest: every prompt the batcher was handed is, token for token,
  the prompt the generator sent (hashes of the decoded token rows,
  recorded where the served process hands them to the scheduler);
- every answer has exactly the tokens asked for (no stop token is sent);
- the served tokens themselves: a sample drawn from the seed of the
  window's answered requests, the longest among them, some hundreds of
  served tokens, is run through the float32 reference once, prompt and
  served tokens together, and the widest gap by which a served token's
  reference logit lies below the reference's best is held to the
  configuration's limit.  That covers the paged step's logits after
  chunked prefill and after decoding through the cache, and the greedy
  selection of each token.

Each number is printed beside its limit.  The limits and the readings they
were set from are in the configuration file and in PERF.md.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Tuple

import numpy as np

from . import traffic

SAMPLE_TOKENS = 384     # served tokens the reference reads at least
SAMPLE_MAX = 8          # requests it reads at most


def digest(tokens: np.ndarray) -> bytes:
    """Hash of one prompt row as the scheduler receives it."""
    row = np.ascontiguousarray(np.asarray(tokens).astype(np.int32).reshape(-1))
    return hashlib.blake2b(row.tobytes(), digest_size=16).digest()


def sample(records: List[dict], plan: "traffic.Plan", seed: int
           ) -> List[dict]:
    """The window's answered requests the reference reads: the longest
    one, then others in an order drawn from the seed, until some hundreds
    of served tokens are covered."""
    by_idx = {r.idx: r for r in plan.requests}
    done = [r for r in records if r["m"] and r["ok"] and r["n"] > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (by_idx[r["i"]].prompt_len + r["n"],
                                       r["i"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed % 2 ** 64, 7]).permutation(len(rest))
    out, tokens = [longest], longest["n"]
    for k in order:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[k])
        tokens += rest[k]["n"]
    return out


def exact_checks(records: List[dict], plan: "traffic.Plan", seed: int,
                 vocab: int, admitted: Dict[bytes, int]) -> Dict[str, dict]:
    """The counts that must be 0: unanswered window requests, prompts the
    scheduler never received as sent, answers of the wrong length."""
    by_idx = {r.idx: r for r in plan.requests}
    unanswered = sum(1 for r in records if r["m"] and not r["ok"])
    mismatch = wrong_len = 0
    for r in records:
        if not r["ok"]:
            continue
        req = by_idx[r["i"]]
        if admitted.get(digest(traffic.prompt_tokens(seed, req, vocab)),
                        0) <= 0:
            mismatch += 1
        if r["n"] != req.max_new:
            wrong_len += 1
    return {"unanswered": {"value": unanswered, "limit": 0},
            "ingest_mismatch": {"value": mismatch, "limit": 0},
            "wrong_length": {"value": wrong_len, "limit": 0}}


def pairs(chosen: List[dict], plan: "traffic.Plan", seed: int, vocab: int
          ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(prompt, served tokens) of each sampled request."""
    by_idx = {r.idx: r for r in plan.requests}
    return [(traffic.prompt_tokens(seed, by_idx[r["i"]], vocab).reshape(-1),
             np.asarray(r["tok"], np.int64)) for r in chosen]


def verdict(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {k}: {c['value']} (limit {c['limit']})"
            for k, c in checks.items()]
