"""Schedule autotuner: the paper's blocking optimizer driving the kernels.

The analytical model (``repro.core``) derives candidate blockings; this
package lowers them to concrete Pallas tile tuples, optionally times the
top few on the actual backend, and persists winners in a JSON cache so
every later process — including the default paths of ``kernels.ops`` —
gets tuned tiles for free.

Entry points:

* :func:`best_schedule` — cheap, never measures: cached schedule if one
  exists for this (op, shapes, dtype, device), else the analytic winner.
  This is what ``kernels.ops`` consults on every call with ``tiles=None``.
* :func:`tune_op` — the full loop: rank candidates analytically, time the
  top-N, persist the winner.  Run offline (``python -m repro.tune ...``)
  to pre-populate the cache; see ``docs/tuning.md``.
"""

from __future__ import annotations

import functools

from repro.core.tpu_adapter import TPU_V5E, TpuTarget
from repro.tune.cache import ScheduleCache, default_cache_path, device_kind
from repro.tune.lowering import (candidates, divides, fits_vmem,
                                 level0_dram_bytes,
                                 predicted_dram_accesses,
                                 predicted_dram_bytes,
                                 schedule_to_string, vmem_budget)
from repro.tune.schedule import OpSpec, Schedule

__all__ = [
    "OpSpec", "Schedule", "ScheduleCache", "best_schedule", "candidates",
    "default_cache_path", "describe_candidates", "device_kind",
    "level0_dram_bytes",
    "predicted_dram_accesses", "predicted_dram_bytes",
    "schedule_to_string", "set_default_cache", "set_schedule_observer",
    "tune_op",
]

_default_cache = ScheduleCache()


def set_default_cache(cache: ScheduleCache) -> ScheduleCache:
    """Swap the process-wide schedule cache; returns the previous one.

    Fault injection (``chaos.inject.CorruptScheduleCache``) uses this
    to plant a deliberately bad cached schedule; tests use it to isolate
    cache state.  Also drops the analytic
    memo so the swap is visible to ops already traced once.
    """
    global _default_cache
    prev = _default_cache
    _default_cache = cache
    _derive.cache_clear()
    return prev

# Telemetry tap (repro.obs): one process-wide callable notified of every
# best_schedule resolution with ``(spec, schedule)``.  The observer runs
# at jit TRACE time — it must be cheap and must not call back into
# best_schedule.  ``None`` (the default) costs one comparison.
_SCHEDULE_OBSERVER = None


def set_schedule_observer(fn):
    """Install ``fn(spec, schedule)`` as the resolution observer;
    returns the previous observer (``None`` to uninstall)."""
    global _SCHEDULE_OBSERVER
    prev = _SCHEDULE_OBSERVER
    _SCHEDULE_OBSERVER = fn
    return prev


def describe_candidates(spec: OpSpec, **kwargs) -> str:
    """Human-readable ranked candidate table (CLI / example output)."""
    lines = []
    for i, s in enumerate(candidates(spec, **kwargs)):
        acc = (f"{s.predicted_dram_accesses:.3e}"
               if s.predicted_dram_accesses is not None else "n/a")
        lines.append(f"  #{i}: tiles={s.tiles}  "
                     f"predicted DRAM accesses={acc}")
    return "\n".join(lines)


@functools.lru_cache(maxsize=1024)
def _derive(spec: OpSpec, vmem_budget_bytes: int | None,
            target: TpuTarget) -> Schedule:
    return candidates(spec, vmem_budget_bytes, target)[0]


def best_schedule(op: str, dims: tuple[int, ...], dtype: str = "float32",
                  stride: int = 1,
                  cache: ScheduleCache | None = None,
                  vmem_budget_bytes: int | None = None,
                  target: TpuTarget = TPU_V5E) -> Schedule:
    """Cached-or-derived schedule for one op instance (never measures).

    ``dims`` is ``(M, N, K)`` for the GEMM ops (``"matmul"``,
    ``"matmul_dgrad"``) or output-space ``(X, Y, C, K, Fw, Fh)`` for the
    conv ops (``"conv2d"``, ``"conv2d_dgrad"``, ``"conv2d_wgrad"``) —
    see ``repro.tune.schedule`` for the backward dim conventions.  A
    cache hit (same op,
    shapes, dtype and device kind) wins outright; otherwise the analytic
    top candidate is derived in-process (memoized, not persisted — run
    :func:`tune_op` to measure and persist).
    """
    spec = OpSpec(op, tuple(dims), dtype, stride)
    hit = (cache or _default_cache).lookup(spec)
    if hit is not None and hit.spec == spec and (
            vmem_budget_bytes is None or
            fits_vmem(spec, hit.tiles,
                      vmem_budget(target, vmem_budget_bytes))):
        result = hit
    else:
        result = _derive(spec, vmem_budget_bytes, target)
    obs = _SCHEDULE_OBSERVER
    if obs is not None:
        obs(spec, result)
    return result


def tune_op(op: str, dims: tuple[int, ...], dtype: str = "float32",
            stride: int = 1,
            top_n: int = 3,
            measure: bool = True,
            interpret: bool | None = None,
            cache: ScheduleCache | None = None,
            persist: bool = True,
            vmem_budget_bytes: int | None = None,
            target: TpuTarget = TPU_V5E) -> Schedule:
    """Full tuning loop for one op instance; returns the winner.

    Candidates are ranked by the paper's predicted DRAM accesses; with
    ``measure=True`` the top ``top_n`` are also timed end-to-end (Pallas
    ``interpret=True`` off-TPU) and the fastest wins.  With
    ``persist=True`` the winner lands in the schedule cache under the
    current device kind, where :func:`best_schedule` — and therefore the
    default paths of ``kernels.ops`` — will find it.
    """
    from repro.tune import measure as measure_mod  # lazy: pulls in jax

    spec = OpSpec(op, tuple(dims), dtype, stride)
    ranked = candidates(spec, vmem_budget_bytes, target)
    # only time schedules the kernels can actually run: for non-dividing
    # tiles ops takes its oracle fallback, and timing the oracle would
    # persist a latency the kernel never achieved
    if measure and all(divides(spec, s.tiles) for s in ranked[:top_n]):
        ranked = measure_mod.measure_top(ranked, top_n=top_n,
                                         interpret=interpret)
    winner = ranked[0]
    if persist:
        (cache or _default_cache).store(winner)
    return winner
