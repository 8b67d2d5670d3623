"""Training loop: jitted train_step with grad accumulation, checkpointing,
straggler watchdog and optional gradient compression.

``make_train_step`` builds the jitted step for a (cfg, mesh) pair with
donated params/opt-state (in-place updates on device).  Microbatching uses
``lax.scan`` over gradient-accumulation slices, so the same step function
serves both "fits in memory" and "needs accumulation" regimes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs.trace import span
from repro.optim import adamw
from repro.optim.compress import compress_tree
from repro.train import checkpoint as ckpt


@dataclasses.dataclass
class TrainConfig:
    opt: adamw.AdamWConfig = dataclasses.field(
        default_factory=adamw.AdamWConfig)
    grad_accum: int = 1
    compress_grads: bool = False
    ckpt_dir: str | None = None
    ckpt_every: int = 100
    log_every: int = 10
    straggler_factor: float = 3.0  # step slower than 3x median -> warn
    blocked_linear: bool = False   # projections through the blocked,
    #   custom-VJP Pallas GEMMs (fwd + dgrad kernels with tuned
    #   schedules); off by default — XLA's native dot is the baseline


def make_loss(cfg: ModelConfig, tc: TrainConfig | None = None) -> Callable:
    blocked = bool(tc and tc.blocked_linear)

    def loss(params, batch):
        from repro.kernels import ops
        # the toggle must be live while this fn is TRACED (the branch in
        # ops.linear is a Python-level one), hence inside the loss body
        with ops.blocked_linear(blocked):
            total, metrics = T.loss_fn(cfg, params, batch)
        return total, metrics
    return loss


def make_train_step(cfg: ModelConfig, tc: TrainConfig) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    loss = make_loss(cfg, tc)

    def train_step(params, opt_state, batch):
        if tc.grad_accum > 1:
            def micro(carry, mb):
                gsum, msum = carry
                (l, m), g = jax.value_and_grad(loss, has_aux=True)(
                    params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, msum + l), None

            mbs = jax.tree.map(
                lambda x: x.reshape((tc.grad_accum,
                                     x.shape[0] // tc.grad_accum)
                                    + x.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, ltot), _ = jax.lax.scan(micro, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / tc.grad_accum, grads)
            metrics = {"loss": ltot / tc.grad_accum}
        else:
            (l, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)

        if tc.compress_grads:
            grads, _ = compress_tree(grads)

        params, opt_state, opt_m = adamw.apply_updates(
            tc.opt, params, grads, opt_state)
        metrics = dict(metrics)
        metrics.update(opt_m)
        return params, opt_state, metrics

    return train_step


class StepWatchdog:
    """Straggler mitigation hook: tracks step times, flags anomalies.

    On a real cluster the flag triggers microbatch rebalancing / slice
    eviction; here it logs (the decision logic is what we can test)."""

    def __init__(self, factor: float = 3.0):
        self.factor = factor
        self.times: list[float] = []
        self.flags: list[int] = []

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        window = sorted(self.times[-50:])
        median = window[len(window) // 2]
        slow = len(self.times) > 5 and dt > self.factor * median
        if slow:
            self.flags.append(step)
        return slow


def train(cfg: ModelConfig, tc: TrainConfig, batches, *,
          params=None, rng=None, restore: bool = False,
          log=print, obs=None) -> dict:
    """Single-host training driver (examples use this; launch/train.py
    wraps it with the mesh).

    ``obs`` (an :class:`repro.obs.Obs` bundle, optional) gets the same
    telemetry the serving engines emit: ``train.loss`` /
    ``train.tokens_per_s`` gauges and a ``train.step_us`` histogram in
    the registry, and — with ``blocked_linear`` — every projection's
    schedule resolution in the DRAM ledger under the ``train_step``
    scope.  Every step emits a ``train.step`` span and every save a
    ``train.checkpoint`` span (recorded while a profiler session is
    open).  The loop waits for every step's loss, which the straggler
    watchdog times, so a ``train.step`` span covers the device work.
    """
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if params is None:
        params = T.init_params(cfg, rng)
    opt_state = adamw.init_state(params)
    start_step = 0
    if restore and tc.ckpt_dir:
        latest = ckpt.latest_valid(tc.ckpt_dir)
        if latest is not None:
            state, start_step = ckpt.restore(
                tc.ckpt_dir, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            log(f"restored checkpoint at step {start_step}")

    if obs is not None:
        g_loss = obs.registry.gauge("train.loss")
        g_tps = obs.registry.gauge("train.tokens_per_s")
        h_step = obs.registry.histogram("train.step_us")
        c_steps = obs.registry.counter("train.steps")

    step_fn = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    watchdog = StepWatchdog(tc.straggler_factor)
    history = []
    for step, batch in enumerate(batches, start=start_step):
        t0 = time.perf_counter()
        scope = (obs.dram.scope("train_step") if obs is not None
                 else contextlib.nullcontext())
        with span("train.step", step=step), scope:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        slow = watchdog.observe(step, dt)
        if obs is not None:
            tok = batch.get("tokens") if isinstance(batch, dict) else None
            tokens = (tok.size if tok is not None else
                      max((x.size for x in jax.tree.leaves(batch)), default=0))
            tps = tokens / dt if dt > 0 else 0.0
            g_loss.set(float(metrics["loss"]))
            g_tps.set(round(tps, 1))
            h_step.observe(dt * 1e6)
            c_steps.inc()
            obs.dram.end_step()
        if step % tc.log_every == 0 or slow:
            log(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                f"{dt*1e3:.0f}ms" + ("  [STRAGGLER]" if slow else ""))
        history.append(float(metrics["loss"]))
        if tc.ckpt_dir and (step + 1) % tc.ckpt_every == 0:
            with span("train.checkpoint", step=step + 1):
                ckpt.save_async(tc.ckpt_dir, step + 1,
                                {"params": params, "opt": opt_state})
    ckpt.wait_async()
    return {"params": params, "opt": opt_state, "history": history,
            "straggler_flags": watchdog.flags}
