"""Granite, the dense pre-norm decoder: GQA attention with RoPE and a
SwiGLU MLP in every layer, a tied LM head, and granite's published
scalars folded into the weights.

The program has no knobs for granite's scalars: it scales embeddings
by ``sqrt(hidden_size)`` and scores by ``head_dim ** -0.5``, adds block
outputs unscaled, leaves the logits undivided and normalizes with
``PROGRAM_EPS``.  Each published scalar is folded exactly into the
weights instead, as a checkpoint converter would, with the residual
stream carried at ``k = sqrt(PROGRAM_EPS / rms_norm_eps)`` times the
published one so that the program's RMSNorm epsilon acts as the
published one:

    embedding   x embedding_multiplier * k / sqrt(hidden_size)
    wq          x attention_multiplier / head_dim ** -0.5
    wo, w_down  x residual_multiplier * k
    final norm  / (logits_scaling * embedding_multiplier * k
                   / sqrt(hidden_size))

In exact arithmetic the program then computes the published model
whose weights are the served ones divided by ``folds``, which is what
the plain reference takes.  The published weights are drawn at
``initializer_range``, or at the file's ``init["<leaf>_std"]`` where it
gives one; norm scales are ones.
"""

from __future__ import annotations

import math

import configmap
import weights
import work

MAPPED = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "num_hidden_layers", "vocab_size",
          "rope_theta", "head_dim", "hidden_act", "tie_word_embeddings")
FOLDED = ("rms_norm_eps", "embedding_multiplier", "attention_multiplier",
          "residual_multiplier", "logits_scaling", "initializer_range")
# published values the program has no knob for and computes as these
FIXED = {"attention_bias": False, "mlp_bias": False}
ACT = {"silu": "swiglu"}            # the reference implements SwiGLU
PROGRAM_EPS = 1e-6


def check(c: dict) -> None:
    extra = sorted(set(c) - set(MAPPED) - set(FOLDED) - set(FIXED))
    if extra:
        raise ValueError(f"configuration keys the granite family cannot "
                         f"map: {extra}")
    for k, v in FIXED.items():
        if c.get(k, v) != v:
            raise ValueError(f"{k}={c[k]!r}: the program has only {v!r}")
    if c.get("hidden_act", "silu") not in ACT:
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the reference "
                         f"implements {sorted(ACT)}")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("untied embeddings: the weights and the reference "
                         "implement a tied LM head only")


def model_config(c: dict):
    from repro.models.config import ModelConfig
    heads = int(c["num_attention_heads"])
    return ModelConfig(
        name=c.get("name", "bench"), family="dense",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), n_heads=heads,
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
        d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        mlp_kind=ACT[c.get("hidden_act", "silu")],
        tie_embeddings=bool(c.get("tie_word_embeddings", True)),
        dtype=configmap.dtype(c))


def folds(c: dict) -> dict:
    d = int(c["hidden_size"])
    hd = int(c.get("head_dim") or d // int(c["num_attention_heads"]))
    k = math.sqrt(PROGRAM_EPS / float(c.get("rms_norm_eps", PROGRAM_EPS)))
    emb = float(c.get("embedding_multiplier", math.sqrt(d))) * k \
        / math.sqrt(d)
    resid = float(c.get("residual_multiplier", 1.0)) * k
    return {"embedding": emb,
            "wq": float(c.get("attention_multiplier", hd ** -0.5))
            * math.sqrt(hd),
            "wo": resid, "w_down": resid,
            "final_norm": 1.0 / (float(c.get("logits_scaling", 1.0)) * emb)}


def draw(names, c: dict):
    if "scale" in names:
        return weights.const(1.0)
    std = float(c["initializer_range"])
    for n in names:
        std = c.get("init", {}).get(n + "_std", std)
    return weights.normal(float(std))


def shape(c: dict) -> work.Shape:
    heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return work.Shape(n_layers=int(c["num_hidden_layers"]), d_model=d,
                      n_heads=heads, n_kv_heads=int(c["num_key_value_heads"]),
                      head_dim=int(c.get("head_dim") or d // heads),
                      d_ff=int(c["intermediate_size"]),
                      vocab=int(c["vocab_size"]))
