"""Per-kernel roofline + energy profiler (``python -m repro.profile``).

:class:`KernelProfiler` extends the DRAM ledger's trace/execute-split
attribution (``obs.dram``) from model-predicted bytes to the kernels'
own exact grid-transfer accounting: every kernel in ``repro.kernels``
exports ``hbm_bytes`` — the block transfers its Pallas grid actually
issues, DMA elision included — and the profiler prices each observed
schedule resolution through the matching formula.  Per op key it then
derives:

* **wall time** — scope wall clock (the engine fences every scope when
  a tracer is attached, so scopes measure device time), attributed to
  the ops inside each scope proportionally to their per-execution HBM
  bytes (the memory-bound assumption the paper's model rests on);
* **dispatches** — dispatch *sites* in the traced program x scope
  executions, the same granularity the DRAM ledger attributes bytes
  at: a GEMM inside a ``lax.scan`` over stacked layers counts once per
  trace, not once per layer (resolutions fire at trace time);
* **exact HBM bytes** — per-call ``hbm_bytes`` x dispatch count;
* **achieved vs peak** — arithmetic intensity (2·MACs / bytes) against
  the roofline of the chip this runs on (its ``device_kind`` in
  ``core.tpu_adapter.DEVICE_TARGETS``), reporting the achieved fraction
  of the intensity-limited ceiling.  On a CPU, where the kernels run in
  interpret mode, there is no chip to price against: no share is
  reported;
* **energy** — the paper's model split (``obs.energy``): DRAM priced on
  the measured bytes, SRAM + MAC from the schedule's blocking string.

The **model-fidelity gate** compares the resolved tiles' kernel bytes
against the analytic winner's: a cached schedule moving more than
``fidelity_threshold`` extra traffic is appended to the miss log, where
``python -m repro.tune --from-telemetry`` picks it up for retuning —
stale or corrupted cache entries heal through the normal tuning loop.
"""

from __future__ import annotations

import time

from repro.core.tpu_adapter import target_for
from repro.obs.dram import DramLedger
from repro.obs.energy import op_energy_pj
from repro.tune.schedule import OpSpec, Schedule


def kernel_hbm_bytes(spec: OpSpec, tiles: tuple[int, ...]) -> int | None:
    """Per-dispatch HBM bytes of the kernel serving ``spec`` at ``tiles``
    — the grid's exact block transfers under DMA elision, from the
    kernel's own exported accounting.  ``None`` for tiles the kernel
    cannot run directly (it would take its oracle fallback, whose
    traffic is XLA's business, not ours).

    Decode-attention ops price one (batch=1, kv-head=1) nest instance,
    matching the per-resolution granularity ``best_schedule`` observes
    (one resolution per call site per trace, vmapped batch/head dims
    outside).
    """
    from repro.tune.lowering import divides
    if not divides(spec, tiles):
        return None
    bpe = spec.itemsize
    if spec.op in ("matmul", "matmul_dgrad"):
        from repro.kernels.matmul_blocked import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, bytes_per_elem=bpe)
    if spec.op == "matmul_w8":
        from repro.kernels.matmul_q import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, a_bytes=bpe, w_bytes=1)
    if spec.op == "matmul_fused":
        from repro.kernels.matmul_fused import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, bytes_per_elem=bpe)
    if spec.op == "qkv_fused":
        from repro.kernels.qkv_fused import hbm_bytes
        M, Nkv, K, G = spec.dims
        return hbm_bytes(M, Nkv, K, G, *tiles, bytes_per_elem=bpe)
    if spec.op in ("flash_decode", "flash_decode_fp8"):
        from repro.kernels.flash_decode import hbm_bytes
        G, S, D = spec.dims
        (bkv,) = tiles
        kvb = 1 if spec.op == "flash_decode_fp8" else None
        return hbm_bytes(1, 1, G, D, S, bkv, bytes_per_elem=bpe,
                         kv_bytes=kvb)
    if spec.op == "flash_decode_oproj":
        from repro.kernels.flash_decode import oproj_hbm_bytes
        G, S, D, E = spec.dims
        (bkv,) = tiles
        return oproj_hbm_bytes(1, 1, G, D, E, S, bkv, bytes_per_elem=bpe)
    if spec.op == "conv2d_wgrad":
        from repro.kernels.conv2d_bwd import hbm_bytes
    else:
        from repro.kernels.conv2d_blocked import hbm_bytes
    X, Y, C, K, Fw, Fh = spec.dims
    return hbm_bytes(X, Y, C, K, Fw, Fh, *tiles, bytes_per_elem=bpe,
                     stride=spec.stride)


class KernelProfiler(DramLedger):
    """DRAM ledger + timed scopes + kernel-exact bytes + roofline/energy.

    Drop-in wherever a :class:`~repro.obs.dram.DramLedger` goes
    (``Obs(dram=KernelProfiler(...))``): the engines' existing
    ``obs.dram.scope(tag)`` brackets route here, so serving needs no
    changes to be profiled.  ``tracer`` (optional) receives per-step
    counter tracks (HBM bytes, energy); attach one to the same
    :class:`~repro.obs.Obs` bundle so the engine fences every scope and
    the wall clocks below measure device time, not dispatch time.
    """

    def __init__(self, registry=None, miss_log: str | None = None,
                 fidelity_threshold: float = 0.25,
                 tracer=None):
        import jax
        super().__init__(registry=registry, miss_log=miss_log)
        self.fidelity_threshold = fidelity_threshold
        dev = jax.devices()[0]
        self.target = (None if dev.platform == "cpu"
                       else target_for(dev.device_kind))
        self.tracer = tracer
        self._wall_s: dict[str, float] = {}       # tag -> total scope wall
        self._tag_kbytes: dict[str, int] = {}     # tag -> kernel B / exec
        self._tag_op_counts: dict[str, dict[str, int]] = {}
        self._fid_flagged: set[str] = set()
        self._energy_pj_total = 0.0

    # -- observation ----------------------------------------------------------

    def scope(self, tag: str):
        """Timed version of the ledger scope (same attribution contract)."""
        import contextlib

        @contextlib.contextmanager
        def timed():
            t0 = time.perf_counter()
            try:
                with super(KernelProfiler, self).scope(tag):
                    yield self
            finally:
                self._wall_s[tag] = (self._wall_s.get(tag, 0.0)
                                     + time.perf_counter() - t0)
        return timed()

    def record(self, spec: OpSpec, schedule: Schedule) -> None:
        super().record(spec, schedule)
        key = spec.key(self._device)
        ent = self._ops[key]
        if "kernel_bytes" not in ent:
            from repro import tune
            resolved_b = kernel_hbm_bytes(spec, schedule.tiles)
            analytic = tune.candidates(spec)[0]
            ent["kernel_bytes"] = resolved_b
            ent["kernel_analytic_bytes"] = kernel_hbm_bytes(
                spec, analytic.tiles)
            ent["energy"] = op_energy_pj(spec, schedule.tiles, resolved_b)
            ent["macs"] = spec.problem().macs
        tag = self._tag
        if tag is not None and ent["kernel_bytes"] is not None:
            self._tag_kbytes[tag] = (self._tag_kbytes.get(tag, 0)
                                     + ent["kernel_bytes"])
            counts = self._tag_op_counts.setdefault(tag, {})
            counts[key] = counts.get(key, 0) + 1
        self._check_fidelity(key, ent, spec, schedule)

    def _check_fidelity(self, key: str, ent: dict, spec: OpSpec,
                        schedule: Schedule) -> None:
        """Measured-vs-modeled DRAM gate: resolved tiles moving more
        bytes than the analytic winner by over the threshold are
        appended to the miss log for ``tune --from-telemetry``."""
        if key in self._fid_flagged:
            return
        meas, model = ent["kernel_bytes"], ent["kernel_analytic_bytes"]
        if meas is None or not model:
            # fallback-path tiles never hit the miss log twice: the base
            # ledger already logged them as a plain cache miss
            return
        if meas / model > 1.0 + self.fidelity_threshold:
            self._fid_flagged.add(key)
            self._logged.discard(key)   # force the JSONL append
            self._log_miss(spec, schedule)
            self._logged.add(key)

    def end_step(self, rids=()) -> int:
        n = super().end_step(rids)
        if self.tracer is not None:
            self.tracer.counter("dram", {"bytes_per_step": n})
            self.tracer.counter(
                "energy", {"total_pj": round(self._total_energy_pj(), 1)})
        return n

    # -- aggregation ----------------------------------------------------------

    def _per_op_rollup(self) -> dict[str, dict]:
        """Total dispatches / bytes / wall seconds per op key, combining
        per-trace resolution counts with per-tag execution counts and
        byte-proportional wall-time shares."""
        out: dict[str, dict] = {
            key: {"dispatches": 0, "bytes": 0, "time_s": 0.0}
            for key in self._ops}
        for tag, counts in self._tag_op_counts.items():
            execs = self._execs.get(tag, 0) or 1
            tag_bytes = self._tag_kbytes.get(tag, 0)
            wall = self._wall_s.get(tag, 0.0)
            for key, n_per_exec in counts.items():
                ent = self._ops[key]
                kb = ent.get("kernel_bytes")
                if kb is None:
                    continue
                roll = out[key]
                roll["dispatches"] += n_per_exec * execs
                roll["bytes"] += kb * n_per_exec * execs
                if tag_bytes:
                    roll["time_s"] += wall * (kb * n_per_exec) / tag_bytes
        return out

    def _total_energy_pj(self) -> float:
        total = 0.0
        for key, roll in self._per_op_rollup().items():
            e = self._ops[key].get("energy")
            if e is not None:
                total += e["total_pj"] * roll["dispatches"]
        return total

    def roofline_report(self) -> dict:
        """JSON-safe roofline + energy report, one row per dispatched
        kernel variant.  ``peak_frac`` is achieved FLOP/s over the
        intensity-limited ceiling min(peak, AI x HBM bandwidth); it and
        ``bound`` are left out where there is no chip (``target`` None)."""
        t = self.target
        rows = {}
        totals = {"time_s": 0.0, "bytes": 0, "flops": 0,
                  "energy_pj": 0.0, "dispatches": 0}
        for key, roll in sorted(self._per_op_rollup().items()):
            ent = self._ops[key]
            if not roll["dispatches"]:
                continue
            flops = 2 * ent["macs"] * roll["dispatches"]
            ai = flops / roll["bytes"] if roll["bytes"] else None
            e = ent.get("energy")
            energy_pj = (e["total_pj"] * roll["dispatches"]
                         if e is not None else None)
            row = {
                "tiles": list(ent["tiles"]),
                "source": ent["source"],
                "dispatches": roll["dispatches"],
                "time_us": round(roll["time_s"] * 1e6, 1),
                "hbm_bytes": roll["bytes"],
                "flops": flops,
                "intensity_flops_per_byte": (round(ai, 3)
                                             if ai is not None else None),
                "fidelity_ratio": self._fidelity_ratio(ent),
                "energy_pj": (round(energy_pj, 1)
                              if energy_pj is not None else None),
                "energy_split": e,
            }
            if roll["time_s"] > 0 and ai is not None:
                achieved = flops / roll["time_s"]
                row["achieved_gflops"] = round(achieved / 1e9, 2)
                row["achieved_gbps"] = round(
                    roll["bytes"] / roll["time_s"] / 1e9, 2)
            if roll["time_s"] > 0 and ai is not None and t is not None:
                ceiling = min(t.peak_bf16_flops, ai * t.hbm_bytes_per_s)
                row["peak_frac"] = round(achieved / ceiling, 4)
                row["bound"] = ("memory" if ai * t.hbm_bytes_per_s
                                < t.peak_bf16_flops else "compute")
            rows[key] = row
            totals["time_s"] += roll["time_s"]
            totals["bytes"] += roll["bytes"]
            totals["flops"] += flops
            totals["dispatches"] += roll["dispatches"]
            if energy_pj is not None:
                totals["energy_pj"] += energy_pj
        return {
            "target": (None if t is None else
                       {"name": t.name,
                        "peak_bf16_flops": t.peak_bf16_flops,
                        "hbm_bytes_per_s": t.hbm_bytes_per_s}),
            "fidelity_threshold": self.fidelity_threshold,
            "fidelity_misses": sorted(self._fid_flagged),
            "per_op": rows,
            "totals": {
                "dispatches": totals["dispatches"],
                "time_us": round(totals["time_s"] * 1e6, 1),
                "hbm_bytes": totals["bytes"],
                "flops": totals["flops"],
                "energy_uj": round(totals["energy_pj"] / 1e6, 3),
            },
        }

    @staticmethod
    def _fidelity_ratio(ent: dict) -> float | None:
        meas, model = ent.get("kernel_bytes"), ent.get("kernel_analytic_bytes")
        if meas is None or not model:
            return None
        return round(meas / model, 4)

    def report(self) -> dict:
        out = super().report()
        out["roofline"] = self.roofline_report()
        return out

    def format_roofline(self) -> str:
        """Aligned-text roofline table through the one metrics formatter."""
        from repro.obs.metrics import format_metrics
        rep = self.roofline_report()
        tree = {}
        for key, row in rep["per_op"].items():
            tree[key] = {
                k: v for k, v in row.items()
                if k not in ("tiles", "energy_split") and v is not None}
            tree[key]["tiles"] = "x".join(str(t) for t in row["tiles"])
        tree["TOTAL"] = rep["totals"]
        return format_metrics({"roofline": tree}, sections=["roofline"])
