"""Serving launcher: static-batch or paged continuous-batching engine.

    # static batch (the baseline)
    python -m repro.launch.serve --arch gemma2-9b --reduced \
        --batch 4 --prompt-len 16 --gen 32

    # paged continuous batching (tuned KV page size, mixed prompt lengths)
    python -m repro.launch.serve --arch gemma2-9b --reduced --engine paged \
        --batch 8 --requests 16 --prompt-len 16 --mixed-lens --gen 32

    # quantized serving: int8 weights + fp8 KV page pool (page size from
    # the fp8-aware blocking model; docs/quantization.md)
    python -m repro.launch.serve --arch gemma2-9b --reduced --engine paged \
        --batch 8 --gen 32 --quantize w8fp8
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.sharding import set_axis_mapping
from repro.obs import Obs, format_metrics
from repro.serve.engine import (DecodeEngine, PagedEngine, PagedServeConfig,
                                ServeConfig)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("static", "paged"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4,
                    help="static: batch size; paged: decode batch slots")
    ap.add_argument("--requests", type=int, default=0,
                    help="paged: total requests to stream (default: batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--mixed-lens", action="store_true",
                    help="paged: draw prompt lengths in [prompt_len/2, "
                         "prompt_len]")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged: KV page size (0 -> tuned via the "
                         "flash_decode schedule key)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quantize", choices=("none", "w8", "fp8kv", "w8fp8"),
                    default="none",
                    help="w8: int8 projection weights (matmul_w8 kernel); "
                         "fp8kv: fp8 KV page pool (fp8 flash-decode + "
                         "fp8-aware page size); w8fp8: both")
    ap.add_argument("--fuse", action="store_true",
                    help="cross-op fused kernels on the hot path: "
                         "epilogue-fused MLP GEMMs, one-pass QKV, and "
                         "(paged) oproj-fused flash decode; composes "
                         "with --quantize (docs/fusion.md)")
    ap.add_argument("--prefill-chunk", type=int, default=-1,
                    help="paged: prefill chunk size in tokens (-1 -> "
                         "auto-sized from the VMEM blocking model, 0 -> "
                         "whole-prompt joins; attention-only stacks)")
    ap.add_argument("--spec", type=int, default=0,
                    help="paged: draft tokens per speculative "
                         "draft-verify decode step (0 -> off; greedy "
                         "only, attention-only stacks)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged: radix-tree prefix sharing — repeated "
                         "prompt prefixes reuse cached KV pages "
                         "(copy-on-write; attention-only stacks; "
                         "docs/serving.md)")
    ap.add_argument("--reuse-hint", type=float, default=0.5,
                    help="expected prompt-reuse rate for the "
                         "share-vs-stream page-size pricing (only "
                         "with --prefix-cache)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="paged: per-request wall deadline in seconds; "
                         "requests past it finish DEADLINE_EXCEEDED "
                         "with whatever they emitted "
                         "(docs/robustness.md)")
    ap.add_argument("--preempt", action="store_true",
                    help="paged: allow preempt-with-restore when the "
                         "waiting head starves (greedy only; restored "
                         "requests replay only their unshared tail "
                         "with --prefix-cache)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="paged: per-slot NaN/Inf logit guard — a "
                         "poisoned request FAILs alone instead of "
                         "wedging the batch")
    ap.add_argument("--degrade", action="store_true",
                    help="paged: graceful-degradation ladder driven by "
                         "the metrics registry (no_spec -> small_chunk "
                         "-> preempt)")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write the metrics snapshot (registry + "
                         "modeled-vs-measured DRAM report) as JSON "
                         "(docs/observability.md)")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="record the run in a JAX profiler session under "
                         "DIR: one Perfetto file with the engine's spans "
                         "and the device lanes on one clock (the spans "
                         "never wait for the device)")
    ap.add_argument("--miss-log", metavar="PATH", default=None,
                    help="append schedule-cache misses as JSONL tuning "
                         "targets for python -m repro.tune "
                         "--from-telemetry")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.quantize in ("fp8kv", "w8fp8"):
        cfg = dataclasses.replace(cfg,
                                  kv_cache_dtype=jax.numpy.float8_e4m3fn)
    set_axis_mapping({"data": None, "model": None})
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    if args.quantize in ("w8", "w8fp8"):
        from repro.quant import quantize_params, quantized_bytes
        params = quantize_params(params)
        qb, db = quantized_bytes(params)
        print(f"quantized projection weights: {qb / 1e6:.1f} MB "
              f"(same projections at bf16: {db / 1e6:.1f} MB)")
    rng = np.random.default_rng(0)
    obs = Obs(trace=args.trace, miss_log=args.miss_log)

    def finish_obs(engine) -> None:
        """Shared tail: one formatter for every serve-mode summary."""
        if args.metrics_out:
            engine.obs.write_metrics(args.metrics_out)
            print(f"metrics snapshot -> {args.metrics_out}")
            dram = engine.obs.snapshot()["dram"]
            lines = format_metrics({"dram": {
                k: {kk: v[kk] for kk in
                    ("modeled_bytes", "used_bytes", "ratio")}
                for k, v in dram["per_op"].items()}})
            if lines:
                print("modeled-vs-measured DRAM bytes per op key:")
                print(lines)
        if args.miss_log:
            print(f"schedule-cache miss log -> {args.miss_log} "
                  "(replay: python -m repro.tune --from-telemetry)")
        engine.obs.close()
        if engine.obs.session is not None:
            print(f"perfetto trace -> {engine.obs.session.perfetto_path}")

    if args.engine == "paged":
        engine = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=args.max_seq, max_batch=args.batch,
            page_size=args.page_size or None,
            temperature=args.temperature, fuse=args.fuse,
            prefill_chunk=None if args.prefill_chunk < 0
            else args.prefill_chunk,
            spec_decode=args.spec, prefix_cache=args.prefix_cache,
            reuse_hint=args.reuse_hint, preempt=args.preempt,
            nan_guard=args.nan_guard, degrade=args.degrade), obs=obs)
        n_req = args.requests or args.batch
        lo = max(1, args.prompt_len // 2) if args.mixed_lens \
            else args.prompt_len
        lens = rng.integers(lo, args.prompt_len + 1, n_req)
        prompts = [rng.integers(0, cfg.vocab, (int(L),), dtype=np.int32)
                   for L in lens]
        t0 = time.perf_counter()
        try:
            reqs = engine.generate(prompts, args.gen,
                                   deadline_s=args.deadline or None,
                                   return_requests=True)
        except KeyboardInterrupt:
            # Ctrl-C mid-generate: cancel everything, drain to terminal
            # statuses (freeing every page), and still report what ran
            print("\ninterrupted: draining in-flight requests ...")
            engine.shutdown()
            held = engine.scheduler.allocator.in_use()
            print(format_metrics({"lifecycle": engine.lifecycle_stats()}))
            print(f"page pool drained ({held} pages still held)")
            finish_obs(engine)
            return
        dt = time.perf_counter() - t0
        emitted = sum(r.emitted_total for r in reqs)
        tps = emitted / dt
        print(f"paged engine: page={engine.page_size} "
              f"chunk={engine.prefill_chunk} spec={engine.spec} "
              f"slots={args.batch} requests={n_req}"
              + (" fused" if args.fuse else ""))
        # every summary (spec, prefix cache, lifecycle, step latency)
        # renders through the one metrics formatter — no bespoke
        # f-strings
        sections = {}
        if engine.spec:
            sections["spec"] = engine.spec_stats()
        if engine.prefix_caching:
            sections["prefix_cache"] = engine.prefix_stats()
        if args.deadline or args.preempt or args.nan_guard \
                or args.degrade:
            sections["lifecycle"] = engine.lifecycle_stats()
        snap = engine.obs.registry.snapshot()
        sections["sched"] = {"queue_wait_us": snap["sched"]["queue_wait_us"]}
        sections["engine"] = {k: snap["engine"][k]
                              for k in ("compiles", "compile_s")}
        print(format_metrics(sections))
        statuses = sorted({r.status.value for r in reqs})
        print(f"generated {emitted} tokens over {n_req} requests in "
              f"{dt:.2f}s ({tps:.1f} tok/s), statuses: "
              f"{'/'.join(statuses)}")
        print("sample:", reqs[0].output[:16].tolist())
        finish_obs(engine)
        return

    engine = DecodeEngine(cfg, params,
                          ServeConfig(max_seq=args.max_seq,
                                      temperature=args.temperature,
                                      fuse=args.fuse), obs=obs)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len),
                           dtype=np.int32)
    kwargs = {}
    if cfg.is_encdec:
        kwargs["enc_embeds"] = jax.numpy.asarray(
            rng.standard_normal((args.batch, cfg.encoder_seq,
                                 cfg.d_model)).astype(np.float32) * 0.1,
            cfg.dtype)
    if cfg.prefix_tokens:
        kwargs["prefix_embeds"] = jax.numpy.asarray(
            rng.standard_normal((args.batch, cfg.prefix_tokens,
                                 cfg.d_model)).astype(np.float32) * 0.1,
            cfg.dtype)

    t0 = time.perf_counter()
    out = engine.generate(prompts, args.gen, **kwargs)
    dt = time.perf_counter() - t0
    tps = args.batch * args.gen / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    print("sample:", out[0, :16].tolist())
    finish_obs(engine)


if __name__ == "__main__":
    main()
