"""Declarative parameter trees.

Every module declares its parameters once as a tree of :class:`ParamDef`
(shape + PartitionSpec + init scale).  The same declaration is *built* in
three modes:

* ``init``  — materialize arrays (reduced configs, smoke tests, examples)
* ``shape`` — ``jax.ShapeDtypeStruct`` stand-ins (dry-run, no allocation)
* ``spec``  — the PartitionSpec tree fed to ``jax.jit`` in_shardings

keeping shapes and shardings impossible to de-synchronize.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    spec: P
    scale: float = 1.0          # stddev multiplier for trunc-normal init
    dtype: Any = jnp.bfloat16
    init: str = "normal"        # "normal" | "zeros" | "ones"


def fan_in_scale(fan_in: int) -> float:
    return fan_in ** -0.5


@functools.partial(jax.jit, static_argnums=0)
def _normal_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    # jitted so only the cast result is materialized: drawn eagerly, a
    # stacked leaf would first exist whole in float32
    return (jax.random.truncated_normal(key, -3, 3, d.shape, jnp.float32)
            * d.scale).astype(d.dtype)


def _init_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    return _normal_leaf(d, key)


def build(tree: Any, mode: str, rng: jax.Array | None = None) -> Any:
    """Materialize a ParamDef tree in one of the three modes."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, ParamDef))
    if mode == "spec":
        out = [d.spec for d in leaves]
    elif mode == "shape":
        out = [jax.ShapeDtypeStruct(d.shape, d.dtype) for d in leaves]
    elif mode == "init":
        assert rng is not None
        keys = jax.random.split(rng, max(len(leaves), 1))
        out = [_init_leaf(d, k) for d, k in zip(leaves, keys)]
    else:
        raise ValueError(mode)
    return jax.tree.unflatten(treedef, out)


def retype_defs(tree: Any, dtype: Any) -> Any:
    """Replace the default bf16 weight dtype with ``dtype`` (test configs
    run f32).  Leaves that explicitly request another dtype (fp32 SSM
    decay params etc.) are left alone."""
    def _retype(d: ParamDef) -> ParamDef:
        if d.dtype == jnp.bfloat16:
            return dataclasses.replace(d, dtype=dtype)
        return d
    return jax.tree.map(_retype, tree,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def stack_defs(tree: Any, n: int, stack_spec_axis: Any = None) -> Any:
    """Stack a ParamDef tree ``n`` times along a new leading axis (for
    ``lax.scan`` over homogeneous layer groups)."""
    def _stack(d: ParamDef) -> ParamDef:
        spec = P(stack_spec_axis, *d.spec)
        return ParamDef((n,) + d.shape, spec, d.scale, d.dtype, d.init)
    return jax.tree.map(_stack, tree,
                        is_leaf=lambda x: isinstance(x, ParamDef))
