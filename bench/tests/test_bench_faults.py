"""A run with the timed path broken underneath comes out not correct:
once for each fault a serving cell can have (the paged step returns the
KV pool unchanged; a token is altered where the scheduler picks it)."""
from repro.models.transformer import DecoderLM
from repro.serving.engine import PagedBatcher

from bench.tests import tiny


def test_step_that_returns_its_state_unchanged(monkeypatch):
    step = DecoderLM.paged_step

    def stale(self, params, tokens, pool, *rest):
        logits, _ = step(self, params, tokens, pool, *rest)
        return logits, pool
    monkeypatch.setattr(DecoderLM, "paged_step", stale)
    out = tiny.run(31)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_token_altered_where_it_is_produced(monkeypatch):
    pick = PagedBatcher._next_from

    def altered(self, req, logits, index):
        tok = pick(self, req, logits, index)
        return (tok + 1) % logits.shape[-1] if index == 0 else tok
    monkeypatch.setattr(PagedBatcher, "_next_from", altered)
    out = tiny.run(32)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]
