"""Serving observability: metrics registry, host spans on the JAX
profiler's clock, and modeled-vs-measured DRAM accounting
(docs/observability.md).

:class:`Obs` is the bundle the engines take (``PagedEngine(obs=...)``,
``DecodeEngine(obs=...)``): a :class:`~repro.obs.metrics.MetricsRegistry`
that the engine, scheduler and kv-cache report into; with ``trace=DIR``
a :class:`~repro.obs.trace.ProfilerSession` that records the engines'
spans and the device lanes into one Perfetto file, stopped by
:meth:`Obs.close`; and a :class:`~repro.obs.dram.DramLedger` comparing
the analytical model's predicted DRAM bytes against what the schedule
cache actually resolved, per op key, while logging schedule-cache
misses for ``python -m repro.tune --from-telemetry``.

The engines emit their spans (:func:`~repro.obs.trace.span`) whether or
not a session is open, and never wait for the device to do so.  An
engine constructed without an ``obs`` argument builds a private
``Obs()`` — registry and ledger always on (host-side integer
arithmetic), no profiler session.
"""

from __future__ import annotations

import json
import os

from repro.obs.dram import DramLedger, read_miss_log
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               format_metrics, hist_quantile)
from repro.obs.profile import kernel_hbm_bytes
from repro.obs.trace import CompileWatch, ProfilerSession, instant, span

__all__ = [
    "CompileWatch", "Counter", "DramLedger", "Gauge", "Histogram",
    "MetricsRegistry", "Obs", "ProfilerSession", "format_metrics",
    "hist_quantile", "instant", "kernel_hbm_bytes", "read_miss_log", "span",
]


class Obs:
    """One observability bundle per engine (or shared across engines).

    ``trace`` is a directory: a JAX profiler session starts there now
    and stops at :meth:`close`.  Pass a constructed
    :class:`DramLedger` / :class:`MetricsRegistry` to share instances
    across engines.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 trace: str | os.PathLike | None = None,
                 dram: DramLedger | None = None,
                 miss_log: str | None = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.session = ProfilerSession(trace) if trace is not None else None
        self.dram = dram if dram is not None else DramLedger(
            registry=self.registry, miss_log=miss_log)

    def snapshot(self) -> dict:
        """Registry snapshot plus the DRAM ledger's modeled-vs-measured
        report under ``"dram"`` — JSON-safe plain data."""
        snap = self.registry.snapshot()
        snap["dram"] = self.dram.report()
        return snap

    def write_metrics(self, path: str | os.PathLike) -> None:
        d = os.path.dirname(os.fspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1)
            f.write("\n")

    def close(self) -> None:
        if self.session is not None:
            self.session.close()
        self.dram.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
