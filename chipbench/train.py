"""The training driver: the program's sharded AdamW step on the
configuration's mesh, timed from the host.

Set-up builds the mesh, draws the weights from the seed straight into
the program's parameter shardings, places the AdamW state as the
program's own ``adamw.state_specs`` says, compiles the step and drives
that same step, through the window's own call and feed, through its
first ``REF_STEPS`` steps.  It keeps what the comparison needs: the
parameters before them (on the host), each layer's norm of the first
gradient as the optimizer got it (from the first moment after one
step), the losses, and each layer's norm of the parameters' change.
The window then runs steps for ``seconds``; a step counts once its
loss is on the host.  Afterwards the program's state is freed and the
plain reference follows the first steps in float32.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import configmap
import traffic
import weights
from serve import TRACE_OPTIONS, TRACE_S, CompileClock

REF_STEPS = 3


def slices(tree) -> dict:
    """name -> array of every leaf of a parameter-shaped tree, each
    stacked layer leaf cut into its layers (``layers.3.mixer.wq``)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
        if names[0] == "layers":
            for i in range(x.shape[0]):
                out[".".join(["layers", str(i)] + names[2:])] = (x, i)
        else:
            out[".".join(names)] = (x, None)
    return out


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _norm_diff(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def slice_norms(tree, vocab: int, other=None) -> dict:
    """Norm of each layer's leaf (of ``tree - other`` where given); the
    embedding over its ``vocab`` real rows."""
    out = {}
    mine = slices(tree)
    theirs = slices(other) if other is not None else {}
    for name, (x, i) in mine.items():
        a = x if i is None else x[i]
        if "embedding" in name:
            a = a[:vocab]
        if other is None:
            out[name] = float(_norm(a))
        else:
            y, j = theirs[name]
            b = y if j is None else y[j]
            out[name] = float(_norm_diff(a, b[:vocab] if "embedding" in name
                                         else b))
    return out


class Trainer:
    """The program's compiled step with its state, on the mesh."""

    def __init__(self, cfg_json: dict, t: dict, seed: int, log):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.models.sharding import set_axis_mapping, translate_tree
        from repro.optim import adamw
        from repro.train.loop import TrainConfig, make_train_step
        self.clock = CompileClock()
        self.cfg_json, self.t, self.seed = cfg_json, t, seed
        tc = cfg_json["train"]
        axes = tuple(tc["mesh"])
        shape = tuple(int(tc["mesh"][a]) for a in axes)
        self.chips = int(np.prod(shape))
        self.mesh = make_mesh(shape, axes,
                              devices=jax.devices()[:self.chips])
        set_axis_mapping({"data": ("data",), "model": "model"})
        self.cfg = configmap.model_config(cfg_json)
        model_ax = int(tc["mesh"].get("model", 1))
        specs = translate_tree(T.param_specs(self.cfg, model_ax))
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        shard = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                             is_leaf=is_p)
        state = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                             adamw.state_specs(specs), is_leaf=is_p)
        self.opt_cfg = adamw.AdamWConfig(**tc["opt"])
        self.batch_sharding = NamedSharding(self.mesh, P("data", None))
        with jax.set_mesh(self.mesh):
            self.params = weights.make_weights(
                T.param_shapes(self.cfg, model_ax), cfg_json, seed, shard)
            self.opt = jax.jit(adamw.init_state,
                               out_shardings=state)(self.params)
            self.step_fn = jax.jit(
                make_train_step(self.cfg, TrainConfig(opt=self.opt_cfg)),
                donate_argnums=(0, 1))
        jax.block_until_ready((self.params, self.opt))
        self.tokens_per_step = int(t["batch"]) * int(t["seq_len"])
        self.k = 0
        log(f"mesh {dict(zip(axes, shape))}; {self.cfg.n_layers} layers; "
            f"batch {t['batch']} x {t['seq_len']}")

    def batch(self, k: int) -> dict:
        rows = traffic.batches(self.t, self.seed, k, self.cfg.vocab)
        return jax.device_put({"tokens": rows[:, :-1], "labels": rows[:, 1:]},
                              self.batch_sharding)

    def step(self, batch):
        """Dispatch the next step on ``batch``; returns its loss (a
        device scalar)."""
        with jax.set_mesh(self.mesh):
            self.params, self.opt, m = self.step_fn(self.params, self.opt,
                                                    batch)
        self.k += 1
        return m["loss"]

    def first_steps(self, n: int) -> dict:
        """Run the first ``n`` steps and keep what the reference is
        compared with."""
        vocab = self.cfg.vocab
        p0 = jax.device_get(self.params)
        losses, grad = [], None
        for k in range(n):
            losses.append(float(self.step(self.batch(k))))
            if k == 0:
                b1 = self.opt_cfg.b1
                grad = {name: v / (1 - b1) for name, v in
                        slice_norms(self.opt["mu"], vocab).items()}
        start = jax.device_put(p0, jax.tree.map(lambda x: x.sharding,
                                                self.params))
        change = slice_norms(self.params, vocab, start)
        jax.tree.map(lambda x: x.delete(), start)
        return {"p0": p0, "losses": losses, "grad": grad, "change": change}

    def release(self) -> None:
        for x in jax.tree.leaves((self.params, self.opt)):
            if not x.is_deleted():
                x.delete()


def compare(mine: dict, ref: dict) -> dict:
    """The numbers compared: the worst step's loss gap, and the worst
    layer leaf's gap between the program's and the reference's norm of
    the first gradient and of the change over the first steps, over the
    larger of the reference's norm and the median leaf's.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out.  ``moved_apart`` counts the leaves
    whose change differs from the reference's by half of it or more: a
    leaf left unmoved, or moved double, which no sound run does."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(mine["losses"],
                                                    ref["losses"]))
    g_med = float(np.median(list(ref["grad"].values())))
    keep = [k for k, v in ref["grad"].items() if v >= 1e-3 * g_med]

    def worst(a: dict, b: dict) -> float:
        med = float(np.median([b[k] for k in keep]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in keep)

    a, b = mine["change"], ref["change"]
    return {"loss_gap": loss, "grad_gap": worst(mine["grad"], ref["grad"]),
            "update_gap": worst(a, b),
            "moved_apart": sum(abs(a[k] - b[k]) >= 0.5 * b[k] for k in keep),
            "left_out": len(ref["grad"]) - len(keep)}


def run(cfg_json: dict, traffic_path: str, seed: int, seconds: float,
        trace_dir: str | None, t_proc: float, log) -> dict:
    """One run of a training cell, as ``run.py`` reports it."""
    from references import load as load_reference
    t = traffic.load(traffic_path)
    tr = Trainer(cfg_json, t, seed, log)
    first = tr.first_steps(REF_STEPS)
    log(f"first {REF_STEPS} steps: losses {first['losses']}; compile "
        f"{tr.clock.seconds:.3f}s in {tr.clock.compiles} compilations, "
        f"{tr.clock.hits} cache hits, at {time.perf_counter() - t_proc:.3f}s")
    compiles_at_open = tr.clock.compiles
    nxt = tr.batch(tr.k)
    t_open = time.perf_counter()
    setup_s = t_open - t_proc
    steps, done = [], 0
    trace_at = seconds / 2 - TRACE_S / 2
    trace_span = None
    while True:
        now = time.perf_counter() - t_open
        if trace_dir and trace_span is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=TRACE_OPTIONS)
            trace_span = [now, None]
        elif trace_span and trace_span[1] is None \
                and now >= trace_at + TRACE_S:
            jax.profiler.stop_trace()
            trace_span[1] = now
        if now >= seconds and (trace_span is None or trace_span[1]):
            break
        with jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter() - t_open
            loss = tr.step(nxt)
            nxt = tr.batch(tr.k)
            loss = float(loss)
            t1 = time.perf_counter() - t_open
        steps.append((t0, t1, loss))
        if t1 < seconds:
            done += 1
    compiles = tr.clock.compiles - compiles_at_open
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in tr.mesh.devices.flat)
    tr.release()
    jax.tree.map(lambda x: x.delete(), nxt)
    ref_mod = load_reference(cfg_json["reference"])
    t_ref = time.perf_counter()
    ref = ref_mod.steps(cfg_json, first["p0"],
                        [traffic.batches(t, seed, k, tr.cfg.vocab)
                         for k in range(REF_STEPS)])
    ref_s = time.perf_counter() - t_ref
    cmp = compare(first, ref)
    limits = cfg_json["limits"]
    finite = bool(np.isfinite([s[2] for s in steps]).all())
    return {
        "metrics": {"train_tok_s": done * tr.tokens_per_step / seconds,
                    "setup_s": setup_s},
        "checks": {k: (cmp[k], limits[k])
                   for k in ("loss_gap", "grad_gap", "update_gap")}
        | {"moved_apart": (cmp["moved_apart"], 0),
           "finite_losses": (0 if finite else 1, 0)},
        "info": {"steps_in_window": done, "steps": len(steps),
                 "compiles_in_window": compiles,
                 "compile_s": tr.clock.seconds, "cache_hits": tr.clock.hits,
                 "reference_s": ref_s, "leaves_left_out": cmp["left_out"],
                 "losses_first": first["losses"],
                 "losses_reference": ref["losses"]},
        "attempted": len(steps), "failed": 0 if finite else 1,
        "memory_peak_bytes": peak, "chips": tr.chips,
        "steps": steps, "window_s": seconds, "trace_span": trace_span}
