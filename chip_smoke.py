#!/usr/bin/env python3
"""Bring-up smoke: granite-3-8b at its published widths on a TPU.

    python3 chip_smoke.py                # one chip: paged serving
    python3 chip_smoke.py --four-chips   # four chips: sharded training

One chip.  granite-3-8b (``configs/granite_3_8b.py``) keeps every
published width; only depth is cut, to 20 of 40 layers, because the
whole model in bf16 is larger than one v5e's 16 GB.  Weights are random,
drawn from ``--seed``.  Sixteen requests (prompts of 512-2048 tokens,
48 new tokens each) go through ``PagedEngine.generate`` on 8 slots with
chunked prefill, through three engines in turn, each freeing its page
pool before the next: the reference with the kernels off
(``use_kernel=False``, jnp attention), then the kernels unfused, then
``fuse=True``, so every Pallas kernel on the serving path runs.  The
compiled decode and prefill-chunk programs of each kernel engine must
hold the expected kernels as ``tpu_custom_call`` ops (``kernels.ops``
falls back to jnp silently when tiles do not divide), the reference's
none.  What each kernel engine serves is compared with the reference:
the greedy tokens, and the K/V its batched programs left in the page
pools.  Last, the logits at the last prompt position and at two decode
steps of three prompts are compared with the jnp attention path.

Four chips.  Three AdamW steps of granite-3-8b at full width, cut to 2
layers, on a (data 2, model 2) mesh, with parameters initialized into
their shardings; then the same three steps from the same parameters on
one chip.  The losses and the parameter updates must agree, and the
parameters must be sharded.

Progress goes to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure, or a JAX that finds no TPU, exits non-zero without it.
Everything runs in this one process: a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOKE_DIR = os.path.join(ROOT, ".smoke")   # listed in .gitignore

SERVE_LAYERS = 20         # of 40: 20 layers are ~8.4 GB of bf16 weights
TRAIN_LAYERS = 2
MAX_SEQ = 4096
SLOTS = 8
N_REQUESTS = 16
NEW_TOKENS = 48
PROMPT_LENS = (512, 2048)

# Kernel-vs-jnp logit tolerance, relative to the largest logit.  Weights
# and activations are bf16 and both attention paths accumulate in fp32;
# they differ only in summation order (streamed pages against one dense
# softmax; fused epilogues against separate XLA ops), which flips a bf16
# rounding (2**-8 relative) of a layer output now and then.  Such flips
# carry through the residual stream of every layer: 0.05 is about a
# dozen ulps of the largest logit, room for 20 layers of them, while a
# wrong mask, scale or page moves logits by a good part of their range.
LOGIT_RTOL = 0.05

# Kernel engines against the reference engine, on what they served.
# Tokens: the share of requests whose first AGREE_TOKENS greedy tokens
# all match (a rounding difference flips an argmax only at a near-tie,
# and one flip changes the rest of that request).  With random weights
# and tied embeddings the embedding dominates the residual stream and
# the greedy token mostly repeats the last one, so tokens catch gross
# faults only: a kernel reading another slot's pages still agrees on
# every token.  Pools: the relative gap between the layer-1 K/V the two
# engines left in their page pools (``pool_gaps``).  Layer 1 is the
# first whose K/V depend on attention, and every layer runs the same
# kernels; deeper layers add the rounding of each layer below.  On the
# CPU at full width and 2 layers, that fault reads 0.053 at layer 1
# against 0.0041 sound (0.119 against 0.0042 at the reduced width); on
# a v5e at 20 layers the worst layer reads 0.018 sound, which bounds
# layer 1's sound gap.
AGREE_TOKENS = 4
AGREE_FLOOR = 0.5
POOL_RTOL = 0.025

# Sharded-vs-one-chip tolerances.  The optimizer has no warmup and a
# learning rate of 1e-3, so that every step moves a bf16 parameter by
# many ulps (one ulp of a weight of size fan_in**-0.5 is about 1e-4).
# Both runs start from the same parameters and differ only in the order
# gradients are reduced across the mesh.  LOSS_RTOL bounds the relative
# loss gap; PARAM_RTOL bounds, for the worst parameter leaf, the norm of
# the difference of the two updates over the norm of the one-chip update.
# On 4 CPU devices at the reduced width these read 8.7e-5 and 0.081;
# sharded steps that see only half the batch (one data shard's gradient
# dropped) read 0.051 and 0.948, and no update at all 1 by definition.
LOSS_RTOL = 5e-3
PARAM_RTOL = 0.4
TRAIN_LR = 1e-3

KERNELS = ("flash_decode", "flash_decode_oproj", "flash_decode_fp8",
           "qkv_fused", "matmul_fused", "matmul_w8", "_flash_forward")
# engine name -> its PagedServeConfig switches, and the kernels its
# compiled decode and chunk steps must hold (the reference: none)
ENGINES = {"reference": dict(fuse=False, use_kernel=False),
           "unfused": dict(fuse=False),
           "fused": dict(fuse=True)}
EXPECTED = {"reference": {"decode": set(), "chunk": set()},
            "unfused": {"decode": {"flash_decode"},
                        "chunk": {"flash_decode"}},
            "fused": {"decode": {"flash_decode_oproj", "qkv_fused",
                                 "matmul_fused"},
                      "chunk": {"flash_decode", "qkv_fused",
                                "matmul_fused"}}}


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ------------------------------ bookkeeping --------------------------------


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def count_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels in a compiled program: one count per
    ``tpu_custom_call``, named by the jitted kernel entry point recorded
    in its op metadata."""
    found: collections.Counter = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        names = re.findall(r"jit\((\w+)\)", m.group(1)) if m else []
        kernel = next((n for n in reversed(names) if n in KERNELS), "?")
        found[kernel] += 1
    return found


def free(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


# ------------------------------- one chip ----------------------------------


def serve_cfg():
    from repro.configs.granite_3_8b import CONFIG
    return dataclasses.replace(CONFIG, n_layers=SERVE_LAYERS)


def make_prompts(cfg, n: int, lens: tuple[int, int], seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (int(L),), dtype=np.int32)
            for L in rng.integers(lens[0], lens[1] + 1, n)]


def engine_programs(engine) -> dict:
    """Compiled text of the engine's decode step and prefill-chunk step,
    lowered with its own device state (nothing runs)."""
    import jax
    import jax.numpy as jnp
    b = engine.sc.max_batch
    ones = jnp.ones(b, bool)
    ints = jnp.zeros(b, jnp.int32)
    key = jax.random.PRNGKey(0)
    decode = engine._decode.lower(
        engine.params, engine.cache, engine._cur_tok, engine._block_tables,
        engine._lengths, ones, ints, ints, engine._out_buf, key,
        engine._poison, chunk=engine.sc.decode_chunk)
    c = engine.prefill_chunk
    i32 = jnp.int32(0)
    chunk = engine._get_chunk_fn(c).lower(
        engine.params, engine.cache, jnp.zeros((1, c), jnp.int32), i32,
        engine._block_tables, engine._lengths, i32, i32, i32,
        engine._cur_tok, engine._out_buf, engine._hist, key, engine._poison)
    return {"decode": decode.compile().as_text(),
            "chunk": chunk.compile().as_text()}


def serve_phase(cfg, params, prompts, new_tokens: int, *, fuse: bool,
                max_seq: int = MAX_SEQ, slots: int = SLOTS,
                page_size: int | None = None,
                prefill_chunk: int | None = None,
                use_kernel: bool | None = None,
                interpret: bool | None = None) -> dict:
    """Serve ``prompts`` through one ``PagedEngine`` built as
    ``launch/serve.py`` builds it; returns what came out, a host copy of
    the page pools it leaves behind, the kernels its compiled steps
    hold, and the engine's choices.  Page size and prefill chunk default
    to the blocking model's choice.  Frees the engine's page pool before
    returning."""
    import jax
    import numpy as np
    from repro.serve.engine import PagedEngine, PagedServeConfig
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=max_seq, max_batch=slots, fuse=fuse, page_size=page_size,
        prefill_chunk=prefill_chunk, use_kernel=use_kernel,
        interpret=interpret))
    t0 = time.perf_counter()
    reqs = engine.generate(prompts, new_tokens, return_requests=True)
    wall = time.perf_counter() - t0
    programs = engine_programs(engine)
    out = {"page_size": engine.page_size,
           "prefill_chunk": engine.prefill_chunk,
           "wall_s": wall,
           "statuses": collections.Counter(r.status.value for r in reqs),
           "tokens": sum(len(r.output) for r in reqs),
           "outputs": [r.output for r in reqs],
           "pools": [np.asarray(x)
                     for x in jax.device_get(jax.tree.leaves(engine.cache))],
           "kernels": {k: count_kernels(t) for k, t in programs.items()}}
    free(engine.cache)
    return out


def check_serve(res: dict, n_requests: int, new_tokens: int,
                engine: str) -> None:
    check(set(res["statuses"]) == {"ok"},
          f"request statuses {dict(res['statuses'])}")
    check(res["tokens"] == n_requests * new_tokens,
          f"served {res['tokens']} tokens, want {n_requests * new_tokens}")
    for prog, want in EXPECTED[engine].items():
        found = set(res["kernels"][prog])
        check(want <= found and (want or not found),
              f"{engine} {prog} step holds kernels {sorted(found)}, "
              f"want {sorted(want)}")


def agreement(got, ref, k: int = AGREE_TOKENS) -> float:
    """Share of requests whose first ``k`` served tokens equal the
    reference's."""
    import numpy as np
    return sum(np.array_equal(g[:k], r[:k])
               for g, r in zip(got, ref)) / len(ref)


def pool_gaps(got, ref) -> list[float]:
    """``|got - ref| / |ref|`` of two engines' page pools after serving
    the same requests, for each layer from 1 up (K and V together): the
    K/V those layers wrote are functions of the attention outputs of the
    layers below.  Scheduling does not look at values, so both engines
    put each token in the same page.  Page 0, which inactive slots write
    to, is left out.  Pools are (layers, n_pages, Hkv, page, D)."""
    import numpy as np
    gaps = []
    for layer in range(1, ref[0].shape[0]):
        num = den = 0.0
        for g, r in zip(got, ref):
            a = g[layer, 1:].astype(np.float32)
            b = r[layer, 1:].astype(np.float32)
            num += float(np.sum(np.square(a - b)))
            den += float(np.sum(np.square(b)))
        gaps.append((num / max(den, 1e-30)) ** 0.5)
    return gaps




_PAGED_FNS: dict = {}


def _paged_fns(cfg, page_size: int, max_seq: int, fuse: bool,
               use_kernel: bool | None, interpret: bool | None):
    """Jitted one-request prefill-chunk and decode steps, built from the
    paged engine's own attention steps; one pair per variant."""
    key = (cfg, page_size, max_seq, fuse, use_kernel, interpret)
    if key in _PAGED_FNS:
        return _PAGED_FNS[key]
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.serve import kv_cache as KV
    bt = jnp.arange(1, KV.num_blocks(max_seq, page_size) + 1,
                    dtype=jnp.int32)[None]

    @jax.jit
    def span(params, cache, tokens, start):
        with ops.fused_ops(fuse):
            attn = KV.make_paged_span_step(cfg, bt, max_seq, use_kernel,
                                           interpret)
            return T.decode_step(cfg, params, tokens, cache,
                                 jnp.reshape(start, (1,)), attn_step=attn)

    @jax.jit
    def step(params, cache, tok, pos):
        with ops.fused_ops(fuse):
            attn = KV.make_paged_attn_step(cfg, bt, use_kernel, interpret,
                                           fused=fuse)
            return T.decode_step(cfg, params, tok, cache,
                                 jnp.reshape(pos, (1,)), attn_step=attn)

    _PAGED_FNS[key] = (span, step)
    return span, step


def paged_logits(cfg, params, prompt, *, page_size: int, chunk: int,
                 max_seq: int, fuse: bool, use_kernel: bool | None,
                 interpret: bool | None = None, feed=None):
    """Logits of the last prompt position and of two decode steps,
    computed with the paged engine's own attention steps
    (``make_paged_span_step`` for chunked prefill, ``make_paged_attn_step``
    for decode) on a one-request page pool.  ``feed`` pins the two
    decode inputs (default: greedy from these logits).  The engines
    return tokens only; this bounds the numbers behind them, where the
    token comparison sees a deviation only once it flips an argmax."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import kv_cache as KV

    span, step = _paged_fns(cfg, page_size, max_seq, fuse, use_kernel,
                            interpret)
    cache = KV.init_paged_cache(
        cfg, 1, KV.num_blocks(max_seq, page_size) + 1, page_size)
    n = len(prompt)
    for start in range(0, n, chunk):
        toks = np.zeros((1, chunk), np.int32)
        part = prompt[start:start + chunk]
        toks[0, :len(part)] = part
        logits, cache = span(params, cache, jnp.asarray(toks),
                             jnp.int32(start))
    rows = [logits[0, n - 1 - start, :cfg.vocab]]
    fed = []
    for i in range(2):
        tok = (int(feed[i]) if feed is not None
               else int(jnp.argmax(rows[-1])))
        fed.append(tok)
        logits, cache = step(params, cache, jnp.asarray([tok], jnp.int32),
                             jnp.int32(n + i))
        rows.append(logits[0, :cfg.vocab])
    free(cache)
    return np.stack([np.asarray(r, np.float32) for r in rows]), fed


def logit_phase(cfg, params, prompts, *, page_size: int, chunk: int,
                max_seq: int = MAX_SEQ, interpret: bool | None = None
                ) -> dict:
    """Max |kernel - jnp| logit difference, unfused and fused, against
    the jnp attention path, over the given prompts."""
    import numpy as np
    kw = dict(page_size=page_size, chunk=chunk, max_seq=max_seq,
              interpret=interpret)
    worst = {"unfused": 0.0, "fused": 0.0}
    scale = 0.0
    for p in prompts:
        got, fed = paged_logits(cfg, params, p, fuse=False,
                                use_kernel=True, **kw)
        ref, _ = paged_logits(cfg, params, p, fuse=False,
                              use_kernel=False, feed=fed, **kw)
        fused, _ = paged_logits(cfg, params, p, fuse=True,
                                use_kernel=True, feed=fed, **kw)
        check(np.isfinite(got).all() and np.isfinite(fused).all(),
              "non-finite logits")
        scale = max(scale, float(np.abs(ref).max()))
        worst["unfused"] = max(worst["unfused"],
                               float(np.abs(got - ref).max()))
        worst["fused"] = max(worst["fused"],
                             float(np.abs(fused - ref).max()))
    return {"max_abs_diff": worst, "max_abs_logit": scale,
            "tol": LOGIT_RTOL * max(scale, 1.0)}


def one_chip(seed: int, clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.models import transformer as T
    full = serve_cfg()
    whole = dataclasses.replace(full, n_layers=40)
    shapes = T.param_shapes(full)
    param_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                      for s in jax.tree.leaves(shapes))
    kv_bytes = (SLOTS * MAX_SEQ * full.n_layers * 2 * full.n_kv_heads
                * full.head_dim * 2)
    log(f"config: granite-3-8b, published widths (d_model {full.d_model}, "
        f"heads {full.n_heads}/{full.n_kv_heads}, d_ff {full.d_ff}, "
        f"vocab {full.vocab}); depth cut to {full.n_layers} of 40 layers; "
        f"whole model {whole.param_count() * 2 / 1e9:.2f} GB bf16, "
        f"served {param_bytes / 1e9:.2f} GB weights + "
        f"{kv_bytes / 1e9:.2f} GB KV pages ({SLOTS} slots x {MAX_SEQ})")

    t0 = time.perf_counter()
    params = T.init_params(full, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"params: built from seed {seed} in "
        f"{time.perf_counter() - t0:.1f}s")
    prompts = make_prompts(full, N_REQUESTS, PROMPT_LENS, seed)
    log(f"requests: {N_REQUESTS}, prompt lengths "
        f"{sorted(len(p) for p in prompts)}, {NEW_TOKENS} new tokens each")

    page = chunk = 0
    outputs = {}
    for name, switches in ENGINES.items():
        c0 = clock.seconds
        res = serve_phase(full, params, prompts, NEW_TOKENS, **switches)
        page, chunk = res["page_size"], res["prefill_chunk"]
        outputs[name] = (res["outputs"], res["pools"])
        log(f"serve[{name}]: page {page}, prefill chunk {chunk}, "
            f"{res['tokens']} tokens served in {res['wall_s']:.1f}s "
            f"(compile included), statuses {dict(res['statuses'])}, "
            f"compile {clock.seconds - c0:.1f}s")
        for prog, found in res["kernels"].items():
            log(f"kernels[{name}/{prog}]: {dict(sorted(found.items()))}")
        check_serve(res, N_REQUESTS, NEW_TOKENS, name)

    ref = outputs.pop("reference")
    for name, (toks, pools) in outputs.items():
        agree = agreement(toks, ref[0])
        gaps = pool_gaps(pools, ref[1])
        log(f"served[{name}] vs reference: first {AGREE_TOKENS} tokens "
            f"agree for {agree:.4g} of requests (floor {AGREE_FLOOR}); "
            f"K/V pool gap layer 1 {gaps[0]:.4g} (tol {POOL_RTOL}), "
            f"worst layer {max(gaps):.4g}")
        check(agree >= AGREE_FLOOR, f"{name} engine's tokens agree with "
                                    f"the reference for {agree:.4g}")
        check(gaps[0] <= POOL_RTOL, f"{name} engine's layer-1 K/V differ "
                                    f"from the reference by {gaps[0]:.4g}")

    c0 = clock.seconds
    cmp = logit_phase(full, params, prompts[:3], page_size=page,
                      chunk=chunk)
    log(f"logits vs jnp attention: max|diff| unfused "
        f"{cmp['max_abs_diff']['unfused']:.4g}, fused "
        f"{cmp['max_abs_diff']['fused']:.4g} (max|logit| "
        f"{cmp['max_abs_logit']:.4g}, tol {cmp['tol']:.4g}); "
        f"compile {clock.seconds - c0:.1f}s")
    for k, v in cmp["max_abs_diff"].items():
        check(v <= cmp["tol"], f"{k} logits differ by {v} > {cmp['tol']}")


# ------------------------------ four chips ---------------------------------


def update_gap(start, a, b) -> float:
    """Worst leaf's ``|(a - start) - (b - start)| / |b - start|``: how
    far update ``a`` strays from update ``b``, relative to ``b``."""
    import jax
    import numpy as np
    worst = 0.0
    for p0, pa, pb in zip(*(jax.tree.leaves(t) for t in (start, a, b))):
        p0 = np.asarray(p0, np.float32)
        db = np.asarray(pb, np.float32) - p0
        gap = np.linalg.norm(np.asarray(pa, np.float32) - p0 - db)
        worst = max(worst, float(gap / max(np.linalg.norm(db), 1e-30)))
    return worst


def train_phase(cfg, devices, *, steps: int = 3, seq_len: int = 512,
                batch: int = 8, seed: int = 0) -> dict:
    """``steps`` AdamW steps on a (data 2, model 2) mesh over
    ``devices``, then the same steps from the same parameters on
    ``devices[0]`` alone."""
    import jax
    import numpy as np
    from repro.data.pipeline import make_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import shard_params
    from repro.models.sharding import set_axis_mapping
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, make_train_step

    tc = TrainConfig(opt=adamw.AdamWConfig(lr=TRAIN_LR, warmup_steps=0))
    batches = [make_batch(cfg, seq_len, batch, s, seed=seed)
               for s in range(steps)]
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    set_axis_mapping({"data": ("data",), "model": "model"})
    with jax.set_mesh(mesh):
        params = shard_params(cfg, mesh, jax.random.PRNGKey(seed))
        total = sum(p.nbytes for p in jax.tree.leaves(params))
        local = sum(p.addressable_shards[0].data.nbytes
                    for p in jax.tree.leaves(params))
        # copied through the host: a device-to-device put of a replicated
        # leaf aliases its device-0 shard, which the sharded step donates
        start = jax.device_get(params)
        step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
        opt = adamw.init_state(params)
        sharded = []
        for b in batches:
            params, opt, m = step(params, opt, b)
            sharded.append(float(m["loss"]))
    sharded_params = jax.device_get(params)
    free((params, opt))

    set_axis_mapping({"data": None, "model": None})
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    params = jax.device_put(start, devices[0])
    opt = adamw.init_state(params)
    one = []
    for b in batches:
        params, opt, m = step(params, opt,
                              jax.device_put(b, devices[0]))
        one.append(float(m["loss"]))
    single_params = jax.device_get(params)
    free((params, opt))
    return {"sharded": sharded, "single": one,
            "param_bytes": total, "bytes_on_device0": local,
            "max_rel_diff": max(abs(a - b) / abs(b)
                                for a, b in zip(sharded, one)),
            "update_gap": update_gap(start, sharded_params, single_params),
            "finite": bool(np.isfinite(sharded + one).all())}


def four_chips(seed: int, clock: CompileClock) -> None:
    import jax
    from repro.configs.granite_3_8b import CONFIG
    cfg = dataclasses.replace(CONFIG, n_layers=TRAIN_LAYERS)
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
                             f"{len(devices)}")
    log(f"config: granite-3-8b, published widths, depth cut to "
        f"{cfg.n_layers} of 40 layers; mesh (data 2, model 2)")
    res = train_phase(cfg, devices, seed=seed)
    log(f"params: {res['param_bytes'] / 1e9:.3f} GB total, "
        f"{res['bytes_on_device0'] / 1e9:.3f} GB on device 0")
    log(f"losses: sharded {res['sharded']}, one chip {res['single']}, "
        f"max rel diff {res['max_rel_diff']:.3g} (tol {LOSS_RTOL}); "
        f"compile {clock.seconds:.1f}s")
    log(f"updates: worst leaf's sharded-vs-one-chip gap "
        f"{res['update_gap']:.4g} of the one-chip update "
        f"(tol {PARAM_RTOL})")
    check(res["finite"], "non-finite loss")
    check(res["bytes_on_device0"] <= 0.3 * res["param_bytes"],
          "parameters are not sharded across the mesh")
    check(res["max_rel_diff"] <= LOSS_RTOL,
          f"sharded losses differ from one chip by "
          f"{res['max_rel_diff']:.3g}")
    check(res["update_gap"] <= PARAM_RTOL,
          f"sharded updates differ from one chip by "
          f"{res['update_gap']:.4g}")


# --------------------------------- main ------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase (4 chips)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})")
        return 2
    log(f"device: {dev.platform} / {dev.device_kind} x "
        f"{len(jax.devices())}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"chip_smoke: no repro sources under {ROOT}/src")
        return 2

    # schedules come from the blocking model alone: a fresh tune cache
    # inside the checkout, never the user's ~/.cache
    shutil.rmtree(SMOKE_DIR, ignore_errors=True)
    os.makedirs(SMOKE_DIR)
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(SMOKE_DIR,
                                                  "schedules.json")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed, clock)
        else:
            one_chip(args.seed, clock)
    except SmokeFailure as e:
        log(f"chip_smoke: FAILED: {e}")
        return 1
    log(f"compile seconds: {clock.seconds:.1f} (persistent-cache hits "
        f"{clock.hits}); total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
