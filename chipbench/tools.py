#!/usr/bin/env python3
"""Measurements made once, when a serving cell is defined, and never by
the benchmark's own runs.

    python3 chipbench/tools.py sweep --workload W --seed N --seconds S \\
        --rates 2,3,4
    python3 chipbench/tools.py control --workload W --seeds 1,2,3 \\
        --seconds S

``sweep`` offers the cell's traffic at each rate in turn, in one
process, and prints what was served: the knee is the highest rate at
which the served token rate keeps up with the offered one and the
waiting queue does not grow across the window.  ``control`` serves the
cell's own load for each seed (weights drawn from that seed), scores a
sample of the finished requests with the plain reference, and puts the
reference computed in int8 in the program's place: it prints the
program's widest logit gap and the control's on the same tokens, from
which the limit is set.  Each line also goes to ``out/<tool>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROC = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def emit(tool: str, rec: dict) -> None:
    print(json.dumps(rec), flush=True)
    with open(os.path.join(run.OUT, tool + ".jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")


def load(workload: str):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.find(bench["workloads"], workload, "workload")
    conf = run.find(bench["configs"], cell["config"], "config")
    with open(os.path.join(run.ROOT, conf["file"])) as f:
        cfg_json = json.load(f)
    return cfg_json, os.path.join(HERE, "traffic", cell["traffic"] + ".json")


def sweep(args) -> None:
    import serve
    cfg_json, tpath = load(args.workload)
    srv = serve.Server(cfg_json, tpath, args.seed, T_PROC, run.log)
    for rate in (float(r) for r in args.rates.split(",")):
        d = srv.drive(srv.arrivals(args.seed, args.seconds, rate),
                      args.seconds)
        s = serve.summary(d)
        window = [r for r in d["recs"].values()
                  if 0 <= r.due < args.seconds]
        offered = sum(r.max_new for r in window) / args.seconds
        emit("sweep", {"rate_per_s": rate, "offered_tok_s": offered,
                       **s["metrics"], **s["info"]})
        srv.finish()


def control(args) -> None:
    import adapter
    import serve
    cfg_json, tpath = load(args.workload)
    seeds = [int(x) for x in args.seeds.split(",")]
    srv = serve.Server(cfg_json, tpath, seeds[0], T_PROC, run.log)
    for k, seed in enumerate(seeds):
        if k:
            adapter.set_params(srv.engine, None)
            srv.params = srv.draw(seed)
            adapter.set_params(srv.engine, srv.params)
        d = srv.drive(srv.arrivals(seed, args.seconds), args.seconds)
        s = serve.summary(d)
        srv.finish()
        sc = serve.score(cfg_json, srv.params, d["recs"], seed,
                         control="int8")
        emit("control", {"seed": seed, **sc,
                         "requests_finished": s["info"]["requests_finished"],
                         "serve_tok_s": s["metrics"]["serve_tok_s"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("tool", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default="2,3,4,5,6")
    args = ap.parse_args(argv)
    run.prepare(args.workload)
    {"sweep": sweep, "control": control}[args.tool](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
