"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device planes are the ``/device:TPU:<n>`` planes.  Their ``XLA Ops``
line holds one event per operation run on the device, their ``XLA
Modules`` line one event per program run.  Host spans are the
benchmark's own ``TraceAnnotation`` events (``bench.step``,
``bench.wait``, ``bench.submit``) on the host plane, on the same clock.

What comes out, for the traced window (the first host span's start to
the last one's end):

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the devices; ``window_s``: the window's length;
* ``steps``: for each ``bench.step`` span, the device time of each
  run of a program (module) that started in it, and the device time
  and count of each operation class by the program it ran in;
* ``ops``: device time per operation class (the op name without its
  numeric suffix, or the kernel's name; loops and calls, which hold
  other operations, are not classes); ``collective_s``: time in
  collectives with no other operation running on that device;
* ``wait_s``: host time waiting for arrivals (``bench.wait``);
* ``breakdown``: the operation classes that took most time, and the
  idle gaps summed by the innermost host event open in them.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)
BENCH = ("bench.step", "bench.wait", "bench.submit")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_class(name: str) -> str:
    """The class of an operation from its HLO text:
    ``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion``; a kernel's
    custom call is named after the kernel."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


CONTAINER = ("while", "conditional", "call")    # ops that hold other ops


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def load(path: str):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                evs = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops = evs
                elif line.name == "XLA Modules":
                    mods = evs
            devices[int(m.group(1))] = (ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                       for e in line.events]
                if any(n in BENCH for _, _, n in evs):
                    host.extend(evs)
    return devices, host


def reduce(path: str) -> dict:
    devices, host = load(path)
    spans = sorted((s, s + d, n) for s, d, n in host if n in BENCH)
    lo = spans[0][0] if spans else 0.0
    hi = spans[-1][1] if spans else 0.0
    window = hi - lo
    steps = [{"t0": s, "t1": e, "modules": collections.defaultdict(list),
              "ops": collections.defaultdict(lambda: [0.0, 0])}
             for s, e, n in spans if n == "bench.step"]
    starts = [st["t0"] for st in steps]
    busy, coll = [], 0.0
    ops = collections.Counter()
    gaps = collections.Counter()
    inner = sorted((s, s + d, n) for s, d, n in host)
    for dev, (dev_ops, mods) in sorted(devices.items()):
        ivs = union((s, s + d) for s, d, _ in dev_ops)
        busy.append(clip(ivs, lo, hi))
        comp = union((s, s + d) for s, d, n in dev_ops
                     if not COLLECTIVE.search(n))
        for s, d, n in dev_ops:
            if lo <= s < hi and op_class(n) not in CONTAINER:
                ops[op_class(n)] += d
                if COLLECTIVE.search(n) and dev == min(devices):
                    coll += d - clip(comp, s, s + d)
        if dev != min(devices):
            continue
        mods = sorted(mods)
        for s, d, n in mods:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < steps[k]["t1"]:
                steps[k]["modules"][module_name(n)].append(d)
        mod_starts = [s for s, _, _ in mods]
        for s, d, n in dev_ops:
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= steps[k]["t1"]:
                continue
            if op_class(n) in CONTAINER:
                continue
            j = bisect.bisect_right(mod_starts, s) - 1
            mod = (module_name(mods[j][2])
                   if j >= 0 and s < mods[j][0] + mods[j][1] else "")
            acc = steps[k]["ops"][(mod, op_class(n))]
            acc[0] += d
            acc[1] += 1
        prev = lo
        for s, e in ivs + [[hi, hi]]:
            s, e = max(s, lo), min(e, hi)
            if s > prev:
                gaps[host_at(inner, (prev + s) / 2)] += s - prev
            prev = max(prev, e)
    n_dev = max(len(devices), 1)
    waits = clip(union((s, e) for s, e, n in spans if n == "bench.wait"),
                 lo, hi)
    return {"window_s": window, "busy_s": sum(busy) / n_dev,
            "devices": len(devices), "steps": steps, "ops": dict(ops),
            "collective_s": coll, "wait_s": waits,
            "breakdown": {"device_ops": ops.most_common(10),
                          "idle_gaps": gaps.most_common(10)}}


def module_name(name: str) -> str:
    """``jit_chunk(123)`` -> ``jit_chunk``."""
    return re.sub(r"\(\d+\)$", "", name)


def host_at(events, t: float) -> str:
    """The innermost host event open at ``t`` (none: ``idle``)."""
    best = None
    k = bisect.bisect_right(events, (t, float("inf"), "")) 
    for s, e, n in events[max(0, k - 2000):k]:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "idle"
