"""Per-kernel roofline + energy profiler CLI (docs/observability.md).

    # profile the seeded serving config, print the roofline table
    PYTHONPATH=src python -m repro.profile --smoke

    # machine-readable roofline + Chrome trace + full metrics snapshot
    PYTHONPATH=src python -m repro.profile --smoke --json /tmp/roofline.json \
        --trace /tmp/trace.json --metrics-out /tmp/metrics.json

    # fault injection: corrupt the cached matmul schedules and watch the
    # model-fidelity gate route them into the miss log for retuning
    PYTHONPATH=src python -m repro.profile --smoke --corrupt matmul \
        --miss-log /tmp/miss.jsonl
    PYTHONPATH=src python -m repro.tune --from-telemetry /tmp/miss.jsonl \
        --dry-run

Runs the paged serving engine on the same serving-scale reduced config
the serve benchmark uses, with a :class:`repro.obs.KernelProfiler` in
the ledger slot and a step tracer always attached (the engines fence
every scope when a tracer is present, so scope wall clocks measure
device time).  Every dispatched kernel variant gets measured wall time,
exact HBM bytes from the kernels' own grid-transfer accounting,
arithmetic intensity, modeled pJ and, on a TPU, the achieved share of
that chip's roofline.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time


# the --corrupt fault injector now lives with the rest of the chaos
# harness; re-exported here because docs and tests imported it from
# repro.profile since PR 9
from repro.chaos.inject import CorruptScheduleCache  # noqa: F401,E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="per-kernel roofline + energy profiler")
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized workload (same serving-scale model)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--fuse", action="store_true", default=True,
                    help="profile the cross-op fused hot path (default: "
                         "on — the fused kernels are the schedule-driven "
                         "paths the profiler exists to observe)")
    ap.add_argument("--no-fuse", dest="fuse", action="store_false")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the roofline/energy report as JSON")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="Chrome-trace path (a temp file is used when "
                         "absent: the tracer must be attached so scopes "
                         "are device-fenced)")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write the full metrics snapshot (registry + "
                         "DRAM + roofline) as JSON")
    ap.add_argument("--miss-log", metavar="PATH", default=None,
                    help="append schedule-cache misses AND fidelity-"
                         "gate hits as JSONL tuning targets for "
                         "python -m repro.tune --from-telemetry")
    ap.add_argument("--fidelity-threshold", type=float, default=0.25,
                    help="measured/modeled DRAM ratio above 1+threshold "
                         "sends the op to the miss log for retuning")
    ap.add_argument("--corrupt", metavar="OP", default=None,
                    help="fault injection: serve cache hits with "
                         "pessimal (halved) tiles for ops whose name "
                         "contains OP, e.g. --corrupt matmul")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.gen = 3, 6
        args.prompt_len, args.max_seq, args.max_batch = 8, 32, 3

    # force the Pallas kernel paths (interpret mode off-TPU): the point
    # is observing the schedules the kernels dispatch, not throughput
    os.environ.setdefault("REPRO_FORCE_KERNELS", "1")

    # imports after arg parsing: --help must not pull in jax
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import tune
    from repro.configs import get_reduced
    from repro.models import transformer as T
    from repro.models.sharding import set_axis_mapping
    from repro.obs import KernelProfiler, MetricsRegistry, Obs, StepTracer
    from repro.serve.engine import PagedEngine, PagedServeConfig

    prev_cache = None
    if args.corrupt:
        prev_cache = tune.set_default_cache(
            CorruptScheduleCache(args.corrupt))

    # the serve benchmark's serving-scale reduced model: per-step compute
    # must dominate host dispatch for roofline numbers to mean anything
    cfg = dataclasses.replace(get_reduced(args.arch), dtype=jnp.float32,
                              d_model=256, n_layers=4, n_heads=8,
                              n_kv_heads=4, d_ff=1024, vocab=4096)
    set_axis_mapping({"data": None, "model": None})
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    trace_path = args.trace
    tmp_trace = None
    if trace_path is None:
        tmp_trace = tempfile.NamedTemporaryFile(
            suffix=".trace.json", delete=False)
        tmp_trace.close()
        trace_path = tmp_trace.name
    registry = MetricsRegistry()
    tracer = StepTracer(trace_path)
    profiler = KernelProfiler(
        registry=registry, miss_log=args.miss_log,
        fidelity_threshold=args.fidelity_threshold, tracer=tracer)
    obs = Obs(registry=registry, trace=tracer, dram=profiler)

    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=args.max_seq, max_batch=args.max_batch,
        fuse=args.fuse), obs=obs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (args.prompt_len,),
                            dtype=np.int32) for _ in range(args.requests)]
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.gen)
    wall = time.perf_counter() - t0

    rep = profiler.roofline_report()
    n_ops = len(rep["per_op"])
    print(f"profiled {args.requests} requests x {args.gen} tokens "
          f"in {wall:.2f}s: {n_ops} kernel variants, "
          f"{rep['totals']['dispatches']} dispatches, "
          f"{rep['totals']['hbm_bytes'] / 1e6:.1f} MB HBM, "
          f"{rep['totals']['energy_uj']:.1f} uJ modeled "
          f"(traced -> {trace_path})")
    print(profiler.format_roofline())
    if rep["fidelity_misses"]:
        print(f"fidelity gate (>{1 + args.fidelity_threshold:.2f}x "
              "modeled DRAM): "
              + ", ".join(rep["fidelity_misses"]))
        if args.miss_log:
            print(f"  -> appended to {args.miss_log} (replay: "
                  "python -m repro.tune --from-telemetry "
                  f"{args.miss_log})")

    if args.json:
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
            f.write("\n")
        print(f"roofline report -> {args.json}")
    if args.metrics_out:
        obs.write_metrics(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    obs.close()
    if prev_cache is not None:
        tune.set_default_cache(prev_cache)
    assert out.shape[0] == args.requests, out.shape


if __name__ == "__main__":
    main()
