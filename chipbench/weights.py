"""Random weights from a seed, in the layout the program serves them in,
with the configuration's published scalars folded in.

Each leaf's published value is drawn as the configuration's family says
(``draw``), multiplied by the factor by which the family folds its
published scalars into that leaf (``folds``), and served in the
configuration's dtype; the embedding's rows past ``vocab_size``, which
the program adds to align its shards, are zero.  All of it on the
device, in one jitted call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import configmap


def normal(std: float):
    """A leaf drawn from N(0, std)."""
    return lambda key, shape: jax.random.normal(key, shape,
                                                jnp.float32) * std


def const(value: float):
    """A leaf that holds ``value`` everywhere."""
    return lambda key, shape: jnp.full(shape, value, jnp.float32)


def folds(c: dict) -> dict:
    """Factor by which each folded leaf's served value exceeds its
    published one, by leaf name."""
    return configmap.family(c).folds(c)


def make_weights(shapes, c: dict, seed: int, shardings=None):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes``,
    drawn from ``seed`` in one jitted call, on the default device or
    straight into ``shardings`` (a tree like ``shapes``)."""
    fam = configmap.family(c)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree.structure(shapes)
    f = fam.folds(c)
    names = [tuple(getattr(k, "key", getattr(k, "name", "")) for k in p)
             for p, _ in leaves]
    how = tuple(fam.draw(n, c) for n in names)
    fold = tuple(next((f[k] for k in n if k in f), 1.0) for n in names)
    pad = tuple("embedding" in n for n in names)
    structs = tuple((tuple(s.shape), jnp.dtype(s.dtype)) for _, s in leaves)
    vocab = int(c["vocab_size"])

    def draw(key):
        keys = jax.random.split(key, len(structs))
        out = []
        for k, (shape, dtype), rule, fl, emb in zip(keys, structs, how,
                                                    fold, pad):
            x = rule(k, shape)
            if emb:
                x = x.at[vocab:].set(0.0)
            out.append((x * fl).astype(dtype))
        return out

    out = None if shardings is None else jax.tree.leaves(shardings)
    return jax.tree.unflatten(
        treedef, jax.jit(draw, out_shardings=out)(seed_key(seed)))


def seed_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    key = jax.random.fold_in(jax.random.key(0), seed % (1 << 32))
    return jax.random.fold_in(key, seed >> 32)
