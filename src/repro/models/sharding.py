"""Mesh-aware sharding helpers.

Model code writes PartitionSpecs against *canonical* axis names
("data", "model").  The launcher installs an axis mapping per mesh
(multi-pod: "data" -> ("pod", "data"); unshardable batch: "data" -> None)
and every in-model ``maybe_shard`` constraint is translated through it, so
the same model definition runs on any mesh layout.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

_AXIS_MAPPING: dict[str, Any] = {}


def set_axis_mapping(mapping: dict[str, Any]) -> None:
    global _AXIS_MAPPING
    _AXIS_MAPPING = dict(mapping)


def get_axis_mapping() -> dict[str, Any]:
    return dict(_AXIS_MAPPING)


def translate_spec(spec: P, mapping: dict[str, Any] | None = None) -> P:
    mapping = _AXIS_MAPPING if mapping is None else mapping

    def tr(axis):
        if isinstance(axis, (tuple, list)):
            out = []
            for a in axis:
                m = mapping.get(a, a)
                if m is None:
                    continue
                out.extend(m if isinstance(m, tuple) else (m,))
            return tuple(out) if out else None
        return mapping.get(axis, axis)

    return P(*(tr(a) for a in spec))


def translate_tree(tree: Any, mapping: dict[str, Any] | None = None) -> Any:
    return jax.tree.map(lambda s: translate_spec(s, mapping), tree,
                        is_leaf=lambda x: isinstance(x, P))


def on_mesh() -> bool:
    """True under a ``jax.set_mesh(mesh)`` context with >1 device."""
    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and mesh.size > 1


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    if on_mesh():
        return jax.lax.with_sharding_constraint(x, translate_spec(spec))
    return x
