"""Distributed training launcher.

    python -m repro.launch.train --arch granite-3-8b --steps 100 \
        --reduced --ckpt-dir /tmp/ckpt --restore auto

On hardware this runs under ``jax.distributed.initialize()`` with the
production mesh; on this container it uses whatever devices exist (the
``--reduced`` configs train a real ~1-100M model on CPU).  Fault tolerance:
``--restore auto`` resumes from the newest valid checkpoint; the data
pipeline is stateless-seeked so the trajectory is bit-identical.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.data.pipeline import make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.sharding import set_axis_mapping, translate_tree
from repro.obs import Obs, format_metrics
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainConfig, train


def shard_params(cfg: ModelConfig, mesh, rng: jax.Array):
    """Initialize parameters straight into their shardings on ``mesh``
    (the installed axis mapping translates the model's canonical specs),
    so no device ever holds the whole model."""
    model_ax = dict(mesh.shape).get("model", 1)
    specs = translate_tree(T.param_specs(cfg, model_ax))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: T.init_params(cfg, k, model_ax),
                   out_shardings=shardings)(rng)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--blocked-kernels", action="store_true",
                    help="route projections through the differentiable "
                         "blocked Pallas GEMMs (fwd + tuned dgrad "
                         "schedules; interpret mode off-TPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", choices=["auto", "none"], default="none")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write the metrics snapshot (train gauges + "
                         "modeled-vs-measured DRAM report) as JSON — the "
                         "same flag serving has (docs/observability.md)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome-trace span timeline of every "
                         "train step (step/grad/checkpoint spans + "
                         "loss/throughput counter tracks)")
    ap.add_argument("--miss-log", metavar="PATH", default=None,
                    help="append schedule-cache misses as JSONL tuning "
                         "targets (meaningful with --blocked-kernels)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_production_mesh() if args.production_mesh \
        else make_host_mesh()
    set_axis_mapping({"data": ("data",), "model": "model"}
                     if "model" in mesh.axis_names else
                     {"data": ("data",), "model": None})

    tc = TrainConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                        total_steps=args.steps),
        grad_accum=args.grad_accum,
        compress_grads=args.compress_grads,
        blocked_linear=args.blocked_kernels,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)

    def batches():
        for step in range(args.steps):
            yield make_batch(cfg, args.seq_len, args.batch, step)

    obs = Obs(trace=args.trace, miss_log=args.miss_log)
    with jax.set_mesh(mesh):
        params = shard_params(cfg, mesh, jax.random.PRNGKey(0))
        result = train(cfg, tc, batches(), params=params,
                       restore=args.restore == "auto", obs=obs)
    print(f"final loss: {result['history'][-1]:.4f} "
          f"(start {result['history'][0]:.4f})")
    if args.metrics_out:
        obs.write_metrics(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
        snap = obs.snapshot()
        print(format_metrics({"train": snap.get("train", {})}))
    if args.trace:
        print(f"chrome trace -> {args.trace}")
    if args.miss_log:
        print(f"schedule-cache miss log -> {args.miss_log} "
              "(replay: python -m repro.tune --from-telemetry)")
    obs.close()


if __name__ == "__main__":
    main()
