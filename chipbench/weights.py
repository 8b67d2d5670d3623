"""Random weights from a seed, in the layout the program serves them in,
with the configuration's published scalars folded in.

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
gives one (norm scales are ones; the embedding's rows past
``vocab_size``, which the program adds to align its shards, are zero),
on the device, in one jitted call, and served in the configuration's
dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PROGRAM_EPS = 1e-6
FOLDED = ("rms_norm_eps", "embedding_multiplier", "attention_multiplier",
          "residual_multiplier", "logits_scaling", "initializer_range")


def folds(c: dict) -> dict:
    """Factor by which each folded leaf's served value exceeds its
    published one, by leaf name."""
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


def leaf_draw(path, f: dict, c: dict) -> tuple:
    """(standard deviation, None for a norm scale; fold factor) of the
    leaf at ``path``."""
    names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
    std = float(c["initializer_range"])
    for n in names:
        std = c.get("init", {}).get(n + "_std", std)
    fold = next((f[n] for n in names if n in f), 1.0)
    return (None if "scale" in names else float(std)), fold


def make_weights(shapes, c: dict, seed: int, shardings=None):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes``,
    drawn from ``seed`` in one jitted call, on the default device or
    straight into ``shardings`` (a tree like ``shapes``)."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree.structure(shapes)
    f = folds(c)
    how = tuple(leaf_draw(p, f, c) for p, _ in leaves)
    pad = tuple("embedding" in str(p) for p, _ in leaves)
    structs = tuple((tuple(s.shape), jnp.dtype(s.dtype)) for _, s in leaves)
    vocab = int(c["vocab_size"])

    def draw(key):
        keys = jax.random.split(key, len(structs))
        out = []
        for k, (shape, dtype), (std, fold), emb in zip(keys, structs, how,
                                                        pad):
            x = (jnp.ones(shape, jnp.float32) if std is None else
                 jax.random.normal(k, shape, jnp.float32) * std)
            if emb:
                x = x.at[vocab:].set(0.0)
            out.append((x * fold).astype(dtype))
        return out

    out = None if shardings is None else jax.tree.leaves(shardings)
    return jax.tree.unflatten(
        treedef, jax.jit(draw, out_shardings=out)(seed_key(seed)))


def seed_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    key = jax.random.fold_in(jax.random.key(0), seed % (1 << 32))
    return jax.random.fold_in(key, seed >> 32)
