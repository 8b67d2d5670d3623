"""Per-kernel roofline + energy profiler: kernel-exact byte accounting,
energy pricing, the model-fidelity gate, and the training-loop telemetry
threading (docs/observability.md).

The hypothesis sweep over (shape, tile) space lives in
test_property_profile.py; the equality cases here are deterministic so
the invariant stays covered on minimal installs too.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import tune
from repro.configs import get_reduced
from repro.core import TPU_V5E
from repro.core.energy import DRAM_PJ_PER_16B
from repro.obs import (DramLedger, KernelProfiler, MetricsRegistry, Obs,
                       StepTracer, kernel_hbm_bytes, read_miss_log)
from repro.obs.energy import op_energy_pj
from repro.profile import CorruptScheduleCache
from repro.tune import level0_dram_bytes
from repro.tune.schedule import OpSpec


# ================ kernel accounting == model level-0 traffic ================


@pytest.mark.parametrize("op,dims,dtype,tiles", [
    ("matmul", (256, 512, 256), "float32", (64, 128, 256)),
    ("matmul", (128, 256, 512), "bfloat16", (128, 64, 64)),
    ("matmul_dgrad", (512, 512, 512), "bfloat16", (256, 512, 128)),
    ("matmul_fused", (256, 512, 256), "bfloat16", (64, 64, 512)),
    ("qkv_fused", (128, 64, 256, 4), "bfloat16", (64, 128, 64)),
    ("qkv_fused", (256, 128, 256, 2), "float32", (128, 256, 128)),
    ("flash_decode", (8, 1024, 128), "bfloat16", (128,)),
    ("flash_decode", (4, 2048, 64), "float32", (512,)),
    ("flash_decode_fp8", (8, 1024, 128), "bfloat16", (256,)),
])
def test_kernel_bytes_equal_model_level0(op, dims, dtype, tiles):
    """The kernels' exported grid-transfer accounting and the core
    model's level-0 DRAM traffic agree exactly on dividing tiles — the
    contract the profiler's fidelity gate rests on."""
    spec = OpSpec(op, dims, dtype)
    assert kernel_hbm_bytes(spec, tiles) == level0_dram_bytes(spec, tiles)


def test_w8_kernel_bytes_exceed_model_by_scale_row_only():
    """matmul_w8 streams a per-N fp32 dequant scale row the model's
    operand set doesn't contain; everything else must match."""
    M, N, K = 256, 512, 256
    spec = OpSpec("matmul_w8", (M, N, K), "bfloat16")
    for tiles in [(64, 128, 256), (256, 256, 512), (128, 64, 128)]:
        gm, gn = M // tiles[0], N // tiles[2]
        scale = N * 4 * (gm if gn > 1 else 1)
        assert kernel_hbm_bytes(spec, tiles) - scale == \
            level0_dram_bytes(spec, tiles)


def test_kernel_bytes_none_on_fallback_tiles():
    assert kernel_hbm_bytes(OpSpec("matmul", (128, 128, 128)),
                            (96, 64, 64)) is None


# ============================ energy pricing ================================


def test_op_energy_pj_components_and_units():
    spec = OpSpec("matmul", (256, 256, 256), "bfloat16")
    tiles = (128, 128, 128)
    dram_b = kernel_hbm_bytes(spec, tiles)
    e = op_energy_pj(spec, tiles, dram_b)
    # DRAM term prices the measured bytes at 320 pJ per 16-bit word
    assert e["dram_pj"] == pytest.approx(dram_b / 2.0 * DRAM_PJ_PER_16B)
    assert e["sram_pj"] >= 0.0 and e["mac_pj"] > 0.0
    assert e["total_pj"] == pytest.approx(
        e["dram_pj"] + e["sram_pj"] + e["mac_pj"])
    assert e["pj_per_mac"] == pytest.approx(e["total_pj"] / spec.problem().macs)
    # per-MAC cost is bounded below by the MAC energy itself
    assert e["pj_per_mac"] > 1.0
    assert op_energy_pj(spec, (96, 64, 64), None) is None


# ===================== profiler roofline aggregation ========================


def test_profiler_rooflines_observed_resolutions():
    reg = MetricsRegistry()
    prof = KernelProfiler(registry=reg)
    with prof.scope("gemm[64]"):        # first execution traces: resolution
        tune.best_schedule("matmul", (64, 64, 64))
    with prof.scope("gemm[64]"):        # steady state: no re-resolution
        pass
    prof.end_step([0])
    rep = prof.roofline_report()
    (key,) = rep["per_op"]
    assert key.startswith("matmul/m64n64k64/")
    row = rep["per_op"][key]
    # one dispatch site per trace x two scope executions
    assert row["dispatches"] == 2
    assert row["hbm_bytes"] == 2 * kernel_hbm_bytes(
        OpSpec("matmul", (64, 64, 64)), tuple(row["tiles"]))
    assert row["flops"] == 2 * (64 ** 3) * 2
    assert row["intensity_flops_per_byte"] > 0
    assert row["energy_pj"] > 0
    # analytic resolution: resolved tiles ARE the model winner
    assert row["source"] == "analytic"
    assert row["fidelity_ratio"] == pytest.approx(1.0)
    assert rep["fidelity_misses"] == []
    assert row["time_us"] > 0 and row["achieved_gflops"] >= 0
    # on a CPU there is no chip to price against: no share, no target
    assert rep["target"] is None
    assert "peak_frac" not in row and "bound" not in row
    prof.target = TPU_V5E               # as on a v5e
    row = prof.roofline_report()["per_op"][key]
    assert row["bound"] in ("memory", "compute")
    assert 0 <= row["peak_frac"] <= 1.0   # host-only scope: ~0 of peak
    t = rep["totals"]
    assert t["dispatches"] == 2 and t["hbm_bytes"] == row["hbm_bytes"]
    assert t["energy_uj"] == pytest.approx(row["energy_pj"] / 1e6, abs=1e-3)
    # the full report nests the ledger view plus the roofline, JSON-safe
    full = prof.report()
    assert full["per_op"][key]["ratio"] == pytest.approx(1.0)
    json.dumps(full)
    text = prof.format_roofline()
    assert key in text and "TOTAL" in text


def test_format_roofline_empty_profiler_is_safe():
    assert isinstance(KernelProfiler().format_roofline(), str)


# ========================= model-fidelity gate ==============================


def test_fidelity_gate_routes_corrupt_schedule_to_miss_log(tmp_path, capsys):
    miss = tmp_path / "miss.jsonl"
    prof = KernelProfiler(miss_log=str(miss), fidelity_threshold=0.05)
    spec = OpSpec("matmul_fused", (8, 1024, 256))
    bad = CorruptScheduleCache("matmul").lookup(spec)
    assert bad is not None and bad.source == "cache"
    with prof.scope("decode[8]"):
        prof.record(spec, bad)
    rep = prof.roofline_report()
    (key,) = rep["fidelity_misses"]
    assert key.startswith("matmul_fused/m8n1024k256/")
    assert rep["per_op"][key]["fidelity_ratio"] > 1.05
    prof.close()
    # the miss-log line keeps the corrupt tiles and cache provenance
    (line,) = [json.loads(l) for l in miss.read_text().splitlines()]
    assert line["source"] == "cache"
    assert tuple(line["fallback_tiles"]) == bad.tiles
    # ...and replays as a tuning target through the normal loop
    assert read_miss_log(str(miss)) == [
        {"op": "matmul_fused", "dims": [8, 1024, 256],
         "dtype": "float32", "stride": 1}]
    from repro.tune.__main__ import main as tune_main
    tune_main(["--from-telemetry", str(miss), "--dry-run"])
    assert "would tune matmul_fused/" in capsys.readouterr().out


def test_fidelity_gate_quiet_on_analytic_resolutions(tmp_path):
    miss = tmp_path / "miss.jsonl"
    prof = KernelProfiler(miss_log=str(miss), fidelity_threshold=0.05)
    with prof.scope("gemm"):
        tune.best_schedule("matmul", (64, 64, 64))
    assert prof.roofline_report()["fidelity_misses"] == []
    prof.close()
    # the plain cache-miss line still lands (base-ledger behavior)...
    targets = read_miss_log(str(miss))
    assert [t["op"] for t in targets] == ["matmul"]
    # ...exactly once: the gate never double-appends an analytic op
    assert len(miss.read_text().splitlines()) == 1


def test_set_default_cache_swaps_and_restores():
    spec_dims = (8, 1024, 256)
    prev = tune.set_default_cache(CorruptScheduleCache("matmul"))
    try:
        s = tune.best_schedule("matmul_fused", spec_dims)
        assert s.source == "cache"
        top = tune.candidates(OpSpec("matmul_fused", spec_dims))[0]
        assert s.tiles != top.tiles
    finally:
        tune.set_default_cache(prev)
    assert tune.best_schedule("matmul_fused", spec_dims).source != "cache"


# ====================== training-loop telemetry =============================


def _train_cfg():
    return dataclasses.replace(
        get_reduced("granite-3-8b"), dtype=jnp.float32, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)


def _run_train(cfg, tmp_path, tag, obs=None, steps=4):
    from repro.data.pipeline import make_batch
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import TrainConfig, train
    tc = TrainConfig(
        opt=AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=steps),
        ckpt_dir=str(tmp_path / f"ckpt_{tag}"), ckpt_every=2)
    batches = (make_batch(cfg, 16, 2, step) for step in range(steps))
    return train(cfg, tc, batches, log=lambda *_: None, obs=obs)


def test_train_loop_telemetry_is_observation_not_perturbation(tmp_path):
    """Traced and untraced training produce bit-identical loss
    trajectories; the trace carries step/grad/checkpoint spans and the
    registry the loss/throughput/step-time series."""
    cfg = _train_cfg()
    r_off = _run_train(cfg, tmp_path, "off")

    trace = tmp_path / "train_trace.json"
    reg = MetricsRegistry()
    obs = Obs(registry=reg, trace=StepTracer(str(trace)), dram=DramLedger())
    r_on = _run_train(cfg, tmp_path, "on", obs=obs)
    obs.close()

    assert r_on["history"] == r_off["history"]
    events = json.loads(trace.read_text())
    names = {e["name"] for e in events}
    assert {"step 0", "step 3", "grad", "checkpoint", "train"} <= names
    # every grad span nests inside its step span
    steps = [e for e in events if e["name"].startswith("step ")]
    for g in (e for e in events if e["name"] == "grad"):
        assert any(s["ts"] - 1e-6 <= g["ts"] and
                   g["ts"] + g["dur"] <= s["ts"] + s["dur"] + 1e-6
                   for s in steps)
    ck = [e for e in events if e["name"] == "checkpoint"]
    assert [e["args"]["step"] for e in ck] == [2, 4]
    snap = reg.snapshot()
    assert snap["train"]["steps"] == 4
    assert snap["train"]["loss"] == pytest.approx(r_on["history"][-1])
    assert snap["train"]["tokens_per_s"] > 0
    assert snap["train"]["step_us"]["count"] == 4
