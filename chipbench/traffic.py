"""The one traffic generator: a traffic file's parameters and a seed in,
a schedule of requests (serving) or of token batches (training) out.

A serving file (``"driver": "serve"``) gives

- ``arrivals``, one of
  ``{"process": "poisson", "rate_per_s": r}``: open-loop Poisson;
  ``{"process": "on_off", "rate_per_s": r, "on_s": a, "off_s": b}``:
  Poisson at ``r * (a + b) / a`` in on-phases of ``a`` seconds, each
  followed by ``b`` silent seconds, so the mean rate is ``r``;
  ``{"process": "backlog", "requests": n}``: ``n`` requests all due as
  the lead-in starts;
- ``lead_in_s``: seconds of load offered before the window opens;
- ``prompt_tokens`` and ``output_tokens``: log-normal lengths
  (``median``, ``sigma``) rounded and clipped to ``[min, max]``;
- optionally ``shared_prefix``, ``{"tokens": t, "prefixes": k}``: each
  prompt begins with up to ``t`` tokens (all but its last) of one of
  ``k`` prefixes;
- ``shape_seed``: the arrival times, the lengths and the choice of
  prefix come from it, so every run offers the same work at the same
  times.  The run's ``--seed`` draws the tokens.  (Letting the seed
  reorder the lengths moved the TTFT tail by a factor of three from
  seed to seed, where two runs of one seed agreed within a tenth.)

Outputs are cut so that prompt + output fits ``max_seq``.

A training file (``"driver": "train"``) gives ``batch`` sequences of
``seq_len`` tokens a step; ``batches`` draws each step's tokens from the
run's seed and the step's number, so that no two rows repeat.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float          # s, relative to the window's opening
    prompt: np.ndarray  # (L,) int32
    max_new: int


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def due_times(a: dict, shape, lead: float, horizon_s: float,
              rate: float | None = None) -> np.ndarray:
    """Due times from ``-lead`` up to ``horizon_s`` (``rate`` overrides
    the file's mean rate)."""
    if a["process"] == "backlog":
        return np.full(int(a["requests"]), -lead)
    rate = float(rate if rate is not None else a["rate_per_s"])
    on, off = (float(a["on_s"]), float(a["off_s"])) \
        if a["process"] == "on_off" else (1.0, 0.0)
    if a["process"] not in ("poisson", "on_off"):
        raise ValueError(f"unknown arrival process {a['process']!r}")
    span = (lead + horizon_s) * on / (on + off)     # seconds of on-time
    n = int(span * rate * (on + off) / on * 1.5) + 16
    t = np.cumsum(shape.exponential(on / (rate * (on + off)), n))
    t = t[t < span + on]
    due = np.floor(t / on) * (on + off) + np.mod(t, on) - lead
    return due[due < horizon_s]


def schedule(path: str, seed: int, horizon_s: float, vocab: int,
             max_seq: int, rate: float | None = None) -> list[Arrival]:
    return requests(load(path), seed, horizon_s, vocab, max_seq, rate)


def requests(t: dict, seed: int, horizon_s: float, vocab: int,
             max_seq: int, rate: float | None = None) -> list[Arrival]:
    """Requests due from ``-lead_in_s`` up to ``horizon_s`` after the
    window opens."""
    shape = np.random.default_rng(int(t["shape_seed"]))
    due = due_times(t["arrivals"], shape, float(t["lead_in_s"]), horizon_s,
                    rate)
    n = len(due)
    prompts = _lengths(shape, t["prompt_tokens"], n)
    outputs = _lengths(shape, t["output_tokens"], n)
    rng = np.random.default_rng(seed)
    sp = t.get("shared_prefix")
    if sp:
        which = shape.integers(0, int(sp["prefixes"]), n)
        heads = rng.integers(0, vocab, (int(sp["prefixes"]),
                                        int(sp["tokens"])), dtype=np.int32)
    out = []
    for i in range(n):
        p = int(prompts[i])
        prompt = rng.integers(0, vocab, p, dtype=np.int32)
        if sp:
            k = min(int(sp["tokens"]), p - 1)
            prompt[:k] = heads[which[i], :k]
        out.append(Arrival(float(due[i]), prompt,
                           int(min(outputs[i], max_seq - p))))
    return out


def batches(t: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Step ``step``'s (batch, seq_len + 1) tokens: inputs and, shifted
    by one, labels."""
    rng = np.random.default_rng([seed % (1 << 63), step])
    return rng.integers(0, vocab, (int(t["batch"]), int(t["seq_len"]) + 1),
                        dtype=np.int32)
