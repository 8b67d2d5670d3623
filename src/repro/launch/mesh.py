"""Production meshes (DESIGN.md §5).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism (gradient all-reduce hierarchically scheduled by
XLA: reduce-scatter intra-pod, all-reduce inter-pod).

Every mesh is built here, with every axis ``AxisType.Auto``: sharding
lives on the parameters and the model's ``with_sharding_constraint``
hints, and GSPMD propagates it through ops (the embedding gather among
them) that Explicit axes would make the caller annotate.  Enter a mesh
with ``jax.set_mesh(mesh)``.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> Mesh:
    """A mesh over ``devices`` (default: all) with Auto axis types."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): data-only mesh."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def data_axes(mesh) -> tuple[str, ...]:
    """The axes that carry the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_divisor(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
