import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all surface here.
Artifacts (memory analysis, cost analysis, collective byte counts parsed
from the partitioned HLO) are written to experiments/dryrun/*.json; the
roofline benchmark reads them.

Usage:
    python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod both
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import axis_mapping, input_specs
from repro.models.sharding import set_axis_mapping

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "experiments", "dryrun")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "s64": 8, "u64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _bytes_of_shapes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-kind bytes moved by collectives (per device, from the
    partitioned module).  We count the tensor sizes on each collective
    instruction's definition line (output(s) of the op ~= payload)."""
    out = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        rhs = s.split("=", 1)[1]
        for op in COLLECTIVE_OPS:
            m = re.search(rf"\b{op}(-start)?\(", rhs)
            if m:
                # the result type annotation precedes the op name
                out[op] += _bytes_of_shapes(rhs[:m.start()])
                break
    return out


def _lower_compile(cfg, shape_name, mesh, parallelism="tp_fsdp"):
    t0 = time.time()
    low = input_specs(cfg, shape_name, mesh, parallelism=parallelism)
    with jax.set_mesh(mesh):
        jitted = jax.jit(low.fn, in_shardings=low.in_shardings,
                         out_shardings=low.out_shardings)
        lowered = jitted.lower(*low.args_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return low, compiled, t_lower, t_compile


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, analysis: bool = True,
             parallelism: str = "tp_fsdp", remat: str | None = None,
             kv8: bool = False) -> dict:
    cfg = get_config(arch)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if kv8:
        import jax.numpy as jnp
        cfg = dataclasses.replace(cfg, kv_cache_dtype=jnp.float8_e4m3fn)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mapping = axis_mapping(cfg, SHAPES[shape_name], mesh, parallelism)
    set_axis_mapping(mapping)

    # --- variant 1: deployable (lax.scan layers, Pallas kernels) --------
    # proves the sharding compiles; gives memory analysis + compile time.
    os.environ.pop("REPRO_UNROLL_SCAN", None)
    os.environ.pop("REPRO_REF_ATTENTION", None)
    low, compiled, t_lower, t_compile = _lower_compile(
        cfg, shape_name, mesh, parallelism)
    mem = compiled.memory_analysis()
    mem_stats = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_stats[attr] = getattr(mem, attr, None)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": low.kind,
        "parallelism": parallelism,
        "remat": remat or "block",
        "n_devices": mesh.size,
        "memory": mem_stats,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "ok": True,
    }

    # --- variant 2: analysis (unrolled layers, blocked-jnp attention) ---
    # XLA cost analysis counts while bodies once, so true per-device HLO
    # FLOPs/bytes and per-layer collective bytes come from unrolled
    # lowerings.  Unrolling the full 40-94 layer stacks takes ~8 min per
    # cell on this 1-core box, so we lower 1-cycle and 2-cycle models and
    # extrapolate linearly over the layer groups (exact: per-group cost is
    # layer-count linear; fixed embed/logit cost cancels in the delta).
    if analysis:
        os.environ["REPRO_UNROLL_SCAN"] = "1"
        os.environ["REPRO_REF_ATTENTION"] = "blocked"
        try:
            t0 = time.time()
            pattern = cfg.layer_pattern
            rem = cfg.n_layers % len(pattern)
            n_groups = cfg.n_layers // len(pattern)

            def measure(k_groups: int) -> dict:
                small = dataclasses.replace(
                    cfg, n_layers=k_groups * len(pattern) + rem)
                _, comp, _, _ = _lower_compile(small, shape_name, mesh,
                                               parallelism)
                cost = comp.cost_analysis() or {}
                coll = collective_bytes(comp.as_text())
                return {"flops": cost.get("flops", 0.0),
                        "bytes": cost.get("bytes accessed", 0.0),
                        "coll": coll}

            m1 = measure(1)
            if n_groups > 1:
                m2 = measure(2)
                scale = n_groups - 1
                flops = m1["flops"] + (m2["flops"] - m1["flops"]) * scale
                bytes_ = m1["bytes"] + (m2["bytes"] - m1["bytes"]) * scale
                coll = {k: int(m1["coll"][k] +
                               (m2["coll"][k] - m1["coll"][k]) * scale)
                        for k in m1["coll"]}
            else:
                flops, bytes_, coll = m1["flops"], m1["bytes"], m1["coll"]
            result.update({
                "flops": flops,
                "bytes_accessed": bytes_,
                "collective_bytes": coll,
                "collective_bytes_total": sum(coll.values()),
                "analysis_compile_s": round(time.time() - t0, 1),
                "analysis_method": "1/2-cycle linear extrapolation",
            })
        finally:
            os.environ.pop("REPRO_UNROLL_SCAN", None)
            os.environ.pop("REPRO_REF_ATTENTION", None)

    if verbose:
        f = result.get("flops")
        ba = result.get("bytes_accessed")
        cb = result.get("collective_bytes_total")
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: "
              + (f"flops={f:.3e} bytes={ba:.3e} coll={cb:.3e} "
                 if f is not None else "")
              + f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"  memory: {mem_stats}")
    return result


def artifact_path(arch: str, shape_name: str, multi_pod: bool,
                  parallelism: str = "tp_fsdp") -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    mesh = "2x16x16" if multi_pod else "16x16"
    safe = arch.replace("/", "_").replace(".", "_")
    suffix = "" if parallelism == "tp_fsdp" else f"__{parallelism}"
    return os.path.join(ARTIFACT_DIR,
                        f"{safe}__{shape_name}__{mesh}{suffix}.json")


def run_and_save(arch: str, shape_name: str, multi_pod: bool,
                 force: bool = False,
                 parallelism: str = "tp_fsdp",
                 remat: str | None = None, kv8: bool = False) -> dict:
    path = artifact_path(arch, shape_name, multi_pod, parallelism)
    if remat is not None:
        path = path.replace(".json", f"__remat_{remat}.json")
    if kv8:
        path = path.replace(".json", "__kv8.json")
    if not force and os.path.exists(path):
        with open(path) as f:
            r = json.load(f)
            if r.get("ok"):
                return r
    try:
        # roofline table is single-pod only (spec): multi-pod proves the
        # pod axis shards, no analysis variant needed.
        result = run_cell(arch, shape_name, multi_pod,
                          analysis=not multi_pod,
                          parallelism=parallelism, remat=remat, kv8=kv8)
    except Exception as e:  # record failures — they are bugs to fix
        traceback.print_exc()
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "ok": False, "error": f"{type(e).__name__}: {e}"}
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="off")
    ap.add_argument("--parallelism", default="tp_fsdp",
                    choices=["tp_fsdp", "fsdp"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in todo:
        for mp in pods:
            r = run_and_save(arch, shape_name, mp, force=args.force,
                             parallelism=args.parallelism)
            if not r.get("ok"):
                failures.append((arch, shape_name, mp, r.get("error")))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(todo) * len(pods)} cells compiled OK")


if __name__ == "__main__":
    main()
