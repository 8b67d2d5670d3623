"""A run with the timed path broken underneath must come out not
correct: the served tokens are scored against the plain reference.
And the control: the reference computed in int8, put in the program's
place, reads above the limit that sound runs stay under."""

import time

import pytest

import run
import testkit

SEED = 2**31 + 29


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(str(tmp_path_factory.mktemp("checkout")))


def alter_token(monkeypatch):
    """Row 0 of every sampling call (the first token of every prompt,
    and slot 0's decoded tokens) is replaced by the next id."""
    from repro.serve import engine
    sample = engine.sample_tokens

    def wrong(cfg, logits, temperature, key):
        tok = sample(cfg, logits, temperature, key)
        return tok.at[0].set((tok[0] + 1) % cfg.vocab)

    monkeypatch.setattr(engine, "sample_tokens", wrong)


def keep_state(monkeypatch):
    """Every model step returns the K/V cache it was given: decoded
    positions and prefill chunks are never written to the pages."""
    from repro.models import transformer
    step = transformer.decode_step

    def stale(cfg, params, token, cache, pos, attn_step=None):
        logits, _ = step(cfg, params, token, cache, pos, attn_step)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step", stale)


@pytest.mark.parametrize("fault", [alter_token, keep_state])
def test_broken_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    line = run.run_cell(root, "tiny-chat", SEED, 3.0, False,
                        time.perf_counter())
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [alter_token, keep_state])
def test_broken_backlog_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    line = run.run_cell(root, "tiny-backlog", SEED + 1, 3.0, False,
                        time.perf_counter())
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [11, 13])
def test_int8_control_reads_above_the_limit(root, seed):
    import os
    import serve
    srv = serve.Server(testkit.TINY, os.path.join(
        root, "chipbench", "traffic", "tiny_chat.json"), seed,
        time.perf_counter(), lambda m: None)
    d = srv.drive(srv.arrivals(seed, 3.0), 3.0)
    srv.finish()
    sc = serve.score(testkit.TINY, srv.params, d["recs"], seed,
                     control="int8")
    assert sc["logit_gap"] <= testkit.TINY["limits"]["logit_gap"] \
        < sc["control_gap"]
