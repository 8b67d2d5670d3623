"""A configuration file, in the published config's key names, mapped
onto the program's ``ModelConfig``.

Every key of the file is either mapped onto the program, folded into
the weights the benchmark draws (``weights.FOLDED``), fixed to the one
value the program has, or bookkeeping.  Any other key, or a value that
the program or the plain reference cannot take, is refused, so that a
file never runs as something other than what it states.
"""

from __future__ import annotations

import weights

MAPPED = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "num_hidden_layers", "vocab_size",
          "rope_theta", "head_dim", "hidden_act", "tie_word_embeddings",
          "model_type", "torch_dtype")
# published values the program has no knob for and computes as these
FIXED = {"attention_bias": False, "mlp_bias": False}
BOOKKEEPING = ("source", "deployment", "reduced", "assumed", "folded",
               "init", "serve", "train", "reference", "limits",
               "max_position_embeddings", "name")
DENSE = ("granite", "llama")        # model types of the dense decoder
ACT = {"silu": "swiglu"}            # the reference implements SwiGLU
DTYPE = ("bfloat16", "float32")


def check(c: dict) -> None:
    """Refuse a file with a key or a value that cannot be run as
    stated."""
    known = set(MAPPED) | set(weights.FOLDED) | set(FIXED) | set(BOOKKEEPING)
    extra = sorted(set(c) - known)
    if extra:
        raise ValueError(f"configuration keys the benchmark cannot map: "
                         f"{extra}")
    for k, v in FIXED.items():
        if c.get(k, v) != v:
            raise ValueError(f"{k}={c[k]!r}: the program has only {v!r}")
    if c.get("model_type") not in DENSE:
        raise ValueError(f"model_type {c.get('model_type')!r}: the "
                         f"benchmark maps {DENSE} onto the dense decoder")
    if c.get("hidden_act", "silu") not in ACT:
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the reference "
                         f"implements {sorted(ACT)}")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("untied embeddings: the weights and the reference "
                         "implement a tied LM head only")
    if c.get("torch_dtype", "bfloat16") not in DTYPE:
        raise ValueError(f"torch_dtype {c['torch_dtype']!r}: served in "
                         f"{DTYPE}")


def model_config(c: dict):
    """The program's configuration for file ``c``."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig
    check(c)
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
        dtype=jnp.dtype(c.get("torch_dtype", "bfloat16")))
