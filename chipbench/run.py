#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program under ``src/``.  The cell names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the traffic's ``driver`` names the module
of this directory that runs it (``serve``, ``train``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of a few
seconds in the middle of the window is reduced (``trace_reduce``) and
each per-layer metric of the cell is read by ``layers/<metric>.py``.
The last line of standard output is one JSON object; the numbers
compared for ``correct`` are also the last lines of standard error.
No TPU, or fewer chips than the cell asks for: exit 3 and no result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_PROC = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
CACHE = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, flush=True)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The cell's metrics of one kind: those that list it, and those
    that list no cells."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def read_layer(bench_dir: str, name: str, ctx: dict):
    import layerkit
    return layerkit.load(os.path.join(bench_dir, "layers",
                                      name + ".py")).read(ctx)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_proc: float) -> dict:
    """Everything after the look for a chip: drive the cell, reduce the
    trace, read the metrics, and build the result line."""
    import jax
    import configmap
    import trace_reduce

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find(bench["workloads"], workload, "workload")
    conf = find(bench["configs"], cell["config"], "config")
    with open(os.path.join(root, conf["file"])) as f:
        cfg_json = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic_path = os.path.join(bench_dir, "traffic",
                                cell["traffic"] + ".json")
    trace_dir = (os.path.join(bench_dir, "out", "trace", workload)
                 if trace else None)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    with open(traffic_path) as f:
        name = json.load(f)["driver"]
    if not os.path.isfile(os.path.join(bench_dir, name + ".py")):
        raise SystemExit(f"run.py: no driver {name!r} in {bench_dir}")
    driver = importlib.import_module(name)
    res = driver.run(cfg_json, traffic_path, seed, seconds, trace_dir,
                     t_proc, log)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res.get("chips", 1),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    out = {}
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        ctx = dict(res, trace=red, shape=configmap.shape(cfg_json),
                   device_kind=dev.device_kind)
        for m in cell_metrics(bench, workload, "per_layer"):
            v = read_layer(bench_dir, m["name"], ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    else:
        for m in cell_metrics(bench, workload, "end_to_end"):
            v = res["metrics"].get(m["name"])
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in res["checks"].items()}
    correct = all(v is not None and lim is not None and v <= lim
                  for v, lim in res["checks"].values())
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": out, "device": device}
    if trace:
        line["breakdown"] = red["breakdown"]
    line["info"] = res["info"]
    line["checks"] = checks
    return line


def prepare(workload: str) -> int:
    """Point imports at the program and this directory, keep every cache
    inside the checkout, and make sure the chips the cell needs are
    there.  Returns the number of chips the cell asks for."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chips = find(bench["workloads"], workload, "workload")["chips"]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    os.makedirs(OUT, exist_ok=True)
    # schedules come from the blocking model alone, never ~/.cache
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(OUT, "schedules.json")
    # the TPU runtime would log under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    # a cache directory the machine names is kept; else the checkout's
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return chips


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare(args.workload)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), T_PROC)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
