"""Kernel-exact byte accounting, energy pricing, the schedule-cache swap,
and the training-loop telemetry threading (docs/observability.md).

The hypothesis sweep over (shape, tile) space lives in
test_property_profile.py; the equality cases here are deterministic so
the invariant stays covered on minimal installs too.
"""

import dataclasses
import glob
import os

import jax.numpy as jnp
import pytest

from repro import tune
from repro.chaos.inject import CorruptScheduleCache
from repro.configs import get_reduced
from repro.core.energy import DRAM_PJ_PER_16B
from repro.obs import DramLedger, MetricsRegistry, Obs, kernel_hbm_bytes
from repro.obs.energy import op_energy_pj
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
    model's level-0 DRAM traffic agree exactly on dividing tiles."""
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


# ========================= schedule-cache swap ==============================


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
    trajectories; the profiler trace carries train.step and
    train.checkpoint spans and the registry the loss/throughput/
    step-time series."""
    from jax.profiler import ProfileData
    cfg = _train_cfg()
    r_off = _run_train(cfg, tmp_path, "off")

    reg = MetricsRegistry()
    obs = Obs(registry=reg, trace=tmp_path / "trace", dram=DramLedger())
    try:
        r_on = _run_train(cfg, tmp_path, "on", obs=obs)
    finally:
        obs.close()

    assert r_on["history"] == r_off["history"]
    (pb,) = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for p in ProfileData.from_file(pb).planes
              if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("train.")]
    steps = [e for e in events if e[0] == "train.step"]
    assert [e[3]["step"] for e in steps] == [0, 1, 2, 3]
    ck = [e for e in events if e[0] == "train.checkpoint"]
    assert [e[3]["step"] for e in ck] == [2, 4]
    # a checkpoint is saved between steps, never inside one
    for c in ck:
        assert not any(s[1] <= c[1] < s[2] for s in steps)
    snap = reg.snapshot()
    assert snap["train"]["steps"] == 4
    assert snap["train"]["loss"] == pytest.approx(r_on["history"][-1])
    assert snap["train"]["tokens_per_s"] > 0
    assert snap["train"]["step_us"]["count"] == 4
