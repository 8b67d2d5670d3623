"""The engine's own host spans in a run's profiler trace, for the
readers of the engine-host layer.

The program annotates each ``PagedEngine.step`` (``engine.step``) and
its phases (``engine.*``, ``sched.*``) with ``TraceAnnotation`` spans on
the thread that calls it, the thread that also holds the harness's
``bench.*`` spans, so ``trace_reduce.load`` keeps them.  The window is
the one ``trace_reduce.reduce`` uses: the first ``bench.*`` span's start
to the last one's end.  Only ``engine.step`` spans that lie wholly in it
count.  A program without these spans, or a trace with no device plane
(the CPU), reads as no steps.
"""

from __future__ import annotations

import glob
import os

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, "out", "trace")

_cache: dict = {}


def newest_xplane(trace_dir: str) -> str | None:
    """The ``.xplane.pb`` written last under ``trace_dir``: this run's
    (``run.py`` clears the cell's directory before it traces)."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def engine_steps(devices: dict, host: list) -> list:
    """One dict per ``engine.step`` that lies wholly in the window:
    ``t0``, ``t1``, ``readback_s`` (its ``engine.readback`` spans, where
    the host waits on the device) and ``idle_s`` (the time in it when
    the first device ran no operation)."""
    bench = [(s, s + d) for s, d, n in host if n in trace_reduce.BENCH]
    if not bench or not devices:
        return []
    lo, hi = min(s for s, _ in bench), max(e for _, e in bench)
    steps = sorted((s, s + d) for s, d, n in host
                   if n == "engine.step" and lo <= s and s + d <= hi)
    readbacks = [(s, s + d) for s, d, n in host if n == "engine.readback"]
    ops, _ = devices[min(devices)]
    busy = trace_reduce.union((s, s + d) for s, d, _ in ops)
    out = []
    for t0, t1 in steps:
        rb = sum(e - s for s, e in readbacks if t0 <= s and e <= t1)
        idle = (t1 - t0) - trace_reduce.clip(busy, t0, t1)
        out.append({"t0": t0, "t1": t1, "readback_s": rb, "idle_s": idle})
    return out


def run_steps(ctx) -> list:
    """:func:`engine_steps` of the trace this run wrote (none without a
    trace)."""
    if not ctx.get("trace"):
        return []
    path = newest_xplane(TRACE_DIR)
    if path is None:
        return []
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = engine_steps(*trace_reduce.load(path))
    return _cache[key]
