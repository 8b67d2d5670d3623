"""Elastic re-scaling: rebuild the mesh when the healthy device count
changes and reshard the checkpoint onto it.

At 1000+-node scale, slices fail; the recovery path is:
  1. the watchdog (train loop) or the platform reports a new device count;
  2. ``plan_mesh(n_devices)`` picks the largest (data, model) grid that
     preserves the model-axis divisibility constraints;
  3. the latest checkpoint is restored with the NEW model_ax — parameter
     *shapes* are mesh-independent in this framework (sharding is metadata,
     not layout), so restore is a pure resharding, and optimizer state
     follows the same specs.

``plan_mesh`` is deliberately pure/deterministic so every surviving host
computes the same plan without coordination.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    n_devices: int
    data: int
    model: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.data, self.model)


def _divisors_desc(n: int) -> list[int]:
    return sorted({d for i in range(1, int(n ** 0.5) + 1) if n % i == 0
                   for d in (i, n // i)}, reverse=True)


def plan_mesh(cfg: ModelConfig, n_devices: int,
              prefer_model: int = 16) -> MeshPlan:
    """Largest usable (data, model) grid for the surviving devices.

    model axis must divide the sharded dims (heads, d_ff, experts, vocab
    padding is adaptive) — we require it divides d_model-derived dims and
    prefer the configured size, shrinking by divisors when devices are
    lost."""
    for model in [m for m in _divisors_desc(prefer_model) if m >= 1]:
        if n_devices % model:
            continue
        data = n_devices // model
        if data < 1:
            continue
        # model axis must divide the ffn (and q-heads) sharding
        ffn = cfg.moe_d_ff or cfg.d_ff or cfg.d_model
        heads_ok = cfg.n_heads == 0 or cfg.n_heads % model == 0
        if ffn % model == 0 and heads_ok:
            return MeshPlan(n_devices, data, model)
    return MeshPlan(n_devices, n_devices, 1)


def make_elastic_mesh(plan: MeshPlan):
    from repro.launch.mesh import make_mesh
    return make_mesh(plan.shape, ("data", "model"),
                     devices=jax.devices()[:plan.n_devices])


def reshard_checkpoint(cfg: ModelConfig, ckpt_dir: str, plan: MeshPlan):
    """Restore the newest checkpoint under the new mesh's model_ax."""
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.train import checkpoint as ckpt
    import numpy as np

    shapes = T.param_shapes(cfg, plan.model)
    template = {
        "params": jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype), shapes),
    }
    template["opt"] = {
        "mu": jax.tree.map(lambda s: np.zeros(s.shape, np.float32),
                           shapes),
        "nu": jax.tree.map(lambda s: np.zeros(s.shape, np.float32),
                           shapes),
        "step": np.zeros((), np.int32),
    }
    return ckpt.restore(ckpt_dir, template)
