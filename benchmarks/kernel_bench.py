"""Kernel micro-benchmarks: Pallas (interpret) vs oracle + model-predicted
traffic for the tile choices (analytic; wall-clock on CPU is NOT the TPU
story, so the derived column reports the model's DRAM-traffic ratio),
plus autotuned-vs-hardcoded tile comparisons on the same access model —
for the FORWARD kernels, (ISSUE 2) the custom-VJP BACKWARD nests, and
(ISSUE 4) the QUANTIZED variants (matmul_w8 under its dtype-aware
schedule key), so the BENCH json carries training- and quantization-cost
axes.  ``--dtype`` picks the activation dtype the forward-GEMM
comparisons (incl. matmul_w8) run at — float32 default, bfloat16
mirrors the TPU deployment width; the conv/backward/attention sections
stay float32."""

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed, write_json
from repro.core import (BlockingString, Dim, Loop, Problem, matmul_tiles)
from repro.kernels import ops, ref
from repro.tune import OpSpec, best_schedule, predicted_dram_accesses


def matmul_traffic_ratio(m, n, k) -> float:
    """Model-predicted HBM traffic under a VMEM-sized on-chip level:
    optimizer tile vs untiled GEMM (whose working set spills)."""
    from repro.core import MemLevel, cache_accesses
    levels = [MemLevel.sram("VMEM", 16 * 1024 * 1024), MemLevel.dram()]
    p = Problem.gemm(M=m, N_cols=n, K_reduce=k)
    bm, bk, bn = matmul_tiles(m, n, k, 2)
    tiled = BlockingString(
        [Loop(Dim.C, bk), Loop(Dim.X, bm), Loop(Dim.K, bn),
         Loop(Dim.C, k), Loop(Dim.K, n), Loop(Dim.X, m)], p)
    naive = BlockingString(
        [Loop(Dim.C, k), Loop(Dim.K, n), Loop(Dim.X, m)], p)
    naive_dram = cache_accesses(naive, levels)["DRAM"]
    tiled_dram = cache_accesses(tiled, levels)["DRAM"]
    return naive_dram / max(tiled_dram, 1)


# hardcoded tiles this benchmark shipped with before the autotuner; kept
# as the baseline the tuned schedules are compared against
DEFAULT_MATMUL_TILES = (64, 128, 128)
DEFAULT_CONV_TILES = (13, 13, 32, 64)
DEFAULT_CONV_DGRAD_TILES = (14, 14, 64, 32)


def tuned_vs_default(spec: OpSpec, default_tiles) -> tuple[tuple, str]:
    """Tuned tiles + a derived-column string comparing DRAM accesses."""
    sched = best_schedule(spec.op, spec.dims, spec.dtype,
                          stride=spec.stride)
    tuned = predicted_dram_accesses(spec, sched.tiles)
    default = predicted_dram_accesses(spec, default_tiles)
    verdict = "BEATS" if tuned < default else \
        "matches" if tuned == default else "LOSES-TO"
    return sched.tiles, (f"tuned {sched.tiles} {tuned:.3e} {verdict} "
                         f"default {default_tiles} {default:.3e} "
                         f"DRAM accesses ({sched.source})")


def _mlp_chain_measured_bytes(M: int, D: int, F: int, bpe: int,
                              t_up, t_down, fused: bool) -> int:
    """Exact HBM traffic of the MLP-block chain as the kernels execute
    it (grid block transfers; see ``matmul_fused.hbm_bytes``).  Unfused:
    two plain GEMMs + a standalone GELU pass (read + write M*F) + a
    standalone residual add (2 reads + 1 write of M*D).  Fused: the
    same two GEMMs with the activation absorbed into the first epilogue
    and the residual streamed into the second."""
    from repro.kernels.matmul_fused import hbm_bytes
    up = hbm_bytes(M, F, D, *t_up, bytes_per_elem=bpe)
    down = hbm_bytes(M, D, F, *t_down, bytes_per_elem=bpe,
                     has_residual=fused)
    total = up + down
    if not fused:
        total += 2 * M * F * bpe          # standalone GELU round trip
        total += 3 * M * D * bpe          # residual add: 2 reads + write
    return total


def run_fused(dtype: str = "float32", smoke: bool = False) -> None:
    """Cross-op fusion section (ISSUE 5): the fused MLP-block chain and
    the one-pass QKV projection vs their per-op chains — correctness vs
    the unfused ops, measured DRAM bytes (the kernels' exact grid
    transfers), and the analytical model's predicted savings, which
    must agree in sign and rank with measurement for every config."""
    from repro.core.fusion import FusedProblem, optimize_fused
    from repro.kernels import qkv_fused as qkv_mod
    from repro.tune import vmem_budget

    rng = np.random.default_rng(0)
    jdt = getattr(jnp, dtype)
    bpe = jnp.dtype(jdt).itemsize
    rtol, atol = (2e-2, 2e-2) if dtype == "bfloat16" else (1e-4, 1e-4)
    budget = vmem_budget()

    configs = [(64, 128, 256)] if smoke else \
        [(128, 256, 512), (256, 256, 1024), (256, 512, 512)]
    rows = []
    for M, D, F in configs:
        x = jnp.asarray(rng.normal(size=(M, D)), jdt)
        w_up = jnp.asarray(rng.normal(size=(D, F)) * 0.1, jdt)
        w_down = jnp.asarray(rng.normal(size=(F, D)) * 0.1, jdt)
        h = jnp.asarray(rng.normal(size=(M, D)), jdt)

        t_up = best_schedule("matmul_fused", (M, F, D), dtype).tiles
        t_down = best_schedule("matmul_fused", (M, D, F), dtype).tiles

        # unfused per-op chain (the baseline the fusion replaces)
        u = ops.matmul(x, w_up, tiles=t_up, interpret=True)
        g = jax.nn.gelu(u.astype(jnp.float32)).astype(jdt)
        out_ref = h + ops.matmul(g, w_down, tiles=t_down, interpret=True)

        # fused chain: two kernels, zero elementwise round-trips
        def fused_chain():
            a = ops.matmul_fused(x, w_up, act="gelu", tiles=t_up,
                                 use_kernel=True, interpret=True)
            return ops.matmul_fused(a, w_down, residual=h, tiles=t_down,
                                    use_kernel=True, interpret=True)

        us, out = timed(lambda: np.asarray(fused_chain()))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out_ref, np.float32),
                                   rtol=rtol, atol=atol)

        meas_unfused = _mlp_chain_measured_bytes(M, D, F, bpe, t_up,
                                                 t_down, fused=False)
        meas_fused = _mlp_chain_measured_bytes(M, D, F, bpe, t_up,
                                               t_down, fused=True)
        assert meas_fused < meas_unfused, (meas_fused, meas_unfused)

        fp = FusedProblem.mlp(M, D, F, bytes_per_elem=bpe)
        best = optimize_fused(fp, budget)[0]
        assert best.savings_bytes > 0, best.summary()
        rows.append((M, D, F, meas_unfused - meas_fused,
                     best.savings_bytes))
        emit(f"kernel/mlp_chain_fused_m{M}d{D}f{F}_{dtype}", us,
             f"measured DRAM {meas_fused:.3e}B vs unfused "
             f"{meas_unfused:.3e}B; model predicts "
             f"{best.savings_bytes:.3e}B saved "
             f"({100 * best.savings_frac:.0f}%)",
             measured_fused_bytes=meas_fused,
             measured_unfused_bytes=meas_unfused,
             modeled_fused_bytes=best.fused_bytes,
             modeled_unfused_bytes=best.unfused_bytes)

    # sign agreed above (both savings > 0); rank must agree too
    by_meas = sorted(rows, key=lambda r: r[3])
    by_model = sorted(rows, key=lambda r: r[4])
    assert [r[:3] for r in by_meas] == [r[:3] for r in by_model], \
        ("model/measurement savings rank disagree", rows)

    # one-pass QKV: the activation streams once instead of three times
    M, D = (32, 128) if smoke else (128, 256)
    hkv_w, g_q = D // 2, 2
    x = jnp.asarray(rng.normal(size=(M, D)), jdt)
    wq = jnp.asarray(rng.normal(size=(D, g_q * hkv_w)) * 0.1, jdt)
    wk = jnp.asarray(rng.normal(size=(D, hkv_w)) * 0.1, jdt)
    wv = jnp.asarray(rng.normal(size=(D, hkv_w)) * 0.1, jdt)
    tq = best_schedule("qkv_fused", (M, hkv_w, D, g_q), dtype).tiles
    us, (q_o, k_o, v_o) = timed(lambda: tuple(
        np.asarray(t) for t in ops.qkv_fused(
            x, wq, wk, wv, tiles=tq, use_kernel=True, interpret=True)))
    for got, w in ((q_o, wq), (k_o, wk), (v_o, wv)):
        np.testing.assert_allclose(
            got.astype(np.float32),
            np.asarray(ops.matmul(x, w, interpret=True), np.float32),
            rtol=rtol, atol=atol)
    meas_fused = qkv_mod.hbm_bytes(M, hkv_w, D, g_q, *tq,
                                   bytes_per_elem=bpe)
    from repro.kernels.matmul_fused import hbm_bytes as mm_bytes
    meas_unfused = 0
    for n in (g_q * hkv_w, hkv_w, hkv_w):
        t = best_schedule("matmul", (M, n, D), dtype).tiles
        meas_unfused += mm_bytes(M, n, D, *t, bytes_per_elem=bpe)
    emit(f"kernel/qkv_fused_m{M}d{D}_{dtype}", us,
         f"measured DRAM {meas_fused:.3e}B vs 3-GEMM "
         f"{meas_unfused:.3e}B"
         + (" BEATS" if meas_fused < meas_unfused else " LOSES-TO"),
         measured_fused_bytes=meas_fused,
         measured_unfused_bytes=meas_unfused)

    # oproj-fused flash decode, per request (B=1): the (Hq, hd)
    # attention output never exists in HBM; the unfused pair writes it
    # and reads it back for the projection GEMM.  (At B>1 the fused
    # kernel refetches the wo slab per batch row — docs/fusion.md's
    # "when fusion loses" arithmetic — so the per-request view is the
    # honest one.)
    from repro.kernels.flash_decode import (flash_decode_oproj,
                                            oproj_hbm_bytes,
                                            page_pool_shape,
                                            paged_attention_oproj_ref)
    hkv, g_d, hd, E = (2, 2, 16, 64) if smoke else (2, 4, 32, 256)
    seq = 32 if smoke else 128
    sched = best_schedule("flash_decode_oproj", (g_d, seq, hd, E), dtype)
    page = sched.tiles[0]
    nb = seq // page
    q = jnp.asarray(rng.normal(size=(1, hkv, g_d, hd)), jdt)
    pool = page_pool_shape(nb + 1, hkv, page, hd)
    kp = jnp.asarray(rng.normal(size=pool), jdt)
    vp = jnp.asarray(rng.normal(size=pool), jdt)
    bt = jnp.asarray(1 + rng.permutation(nb).reshape(1, nb), jnp.int32)
    lengths = jnp.asarray([seq - 3], jnp.int32)
    wo = jnp.asarray(rng.normal(size=(hkv, g_d * hd, E)) * 0.1, jdt)
    us, out = timed(lambda: np.asarray(flash_decode_oproj(
        q, kp, vp, bt, lengths, wo, interpret=True)))
    np.testing.assert_allclose(
        out.astype(np.float32),
        np.asarray(paged_attention_oproj_ref(q, kp, vp, bt, lengths, wo),
                   np.float32), rtol=rtol, atol=atol)
    meas_fused = oproj_hbm_bytes(1, hkv, g_d, hd, E, seq, page,
                                 bytes_per_elem=bpe)
    # unfused: identical decode + wo + output traffic, PLUS the
    # attention-output intermediate's write + read-back
    attn_rt = 2 * hkv * g_d * hd * bpe
    meas_unfused = meas_fused + attn_rt
    assert meas_fused < meas_unfused
    emit(f"kernel/flash_decode_oproj_s{seq}e{E}_{dtype}", us,
         f"measured DRAM {meas_fused:.3e}B vs unfused pair "
         f"{meas_unfused:.3e}B (page {page}, per request)",
         measured_fused_bytes=meas_fused,
         measured_unfused_bytes=meas_unfused, page_size=int(page))


def run(dtype: str = "float32") -> None:
    rng = np.random.default_rng(0)
    jdt = getattr(jnp, dtype)
    # interpret-mode kernels accumulate fp32 either way; tolerances track
    # the activation width the comparison runs at
    rtol, atol = (2e-2, 2e-2) if dtype == "bfloat16" else (1e-3, 1e-3)
    # matmul: hardcoded-default tiles vs the autotuner's pick
    a = jnp.asarray(rng.normal(size=(256, 512)), jdt)
    b = jnp.asarray(rng.normal(size=(512, 256)), jdt)
    ref_out = np.asarray(ref.matmul_ref(a, b), np.float32)
    out = ops.matmul(a, b, tiles=DEFAULT_MATMUL_TILES, interpret=True)
    us, _ = timed(lambda: np.asarray(
        ops.matmul(a, b, tiles=DEFAULT_MATMUL_TILES, interpret=True)))
    ratio = matmul_traffic_ratio(4096, 4096, 4096)
    emit(f"kernel/matmul_256x512x256_{dtype}", us,
         f"model DRAM-traffic reduction (4k GEMM) {ratio:.1f}x")
    np.testing.assert_allclose(np.asarray(out, np.float32), ref_out,
                               rtol=rtol, atol=atol)

    mm_spec = OpSpec("matmul", (256, 256, 512), dtype)
    mm_tiles, derived = tuned_vs_default(mm_spec, DEFAULT_MATMUL_TILES)
    us, tuned_out = timed(lambda: np.asarray(
        ops.matmul(a, b, tiles=mm_tiles, interpret=True)))
    np.testing.assert_allclose(np.asarray(tuned_out, np.float32), ref_out,
                               rtol=rtol, atol=atol)
    emit(f"kernel/matmul_256x512x256_tuned_{dtype}", us, derived)

    # QUANTIZED variant: same dims, int8 weight stream, own schedule key
    # — the dtype-aware model ranks its tiles against 1-byte weights
    from repro.kernels.matmul_q import matmul_w8_ref
    from repro.quant import quantize
    w8_spec = OpSpec("matmul_w8", (256, 256, 512), dtype)
    w8_tiles, w8_derived = tuned_vs_default(w8_spec, DEFAULT_MATMUL_TILES)
    qt = quantize(b.astype(jnp.float32), "int8")
    scale = qt.scale.reshape(-1)
    us, q_out = timed(lambda: np.asarray(
        ops.matmul_w8(a, qt.q, scale, tiles=w8_tiles, interpret=True)))
    np.testing.assert_allclose(
        np.asarray(q_out, np.float32),
        np.asarray(matmul_w8_ref(a, qt.q, scale), np.float32),
        rtol=rtol, atol=atol)
    emit(f"kernel/matmul_w8_256x512x256_tuned_{dtype}", us, w8_derived)

    # matmul BACKWARD: the two dgrad nests (dA: (M,K,N); dB: (K,N,M)),
    # tuned vs the hardcoded default on predicted DRAM accesses, plus the
    # end-to-end jax.grad wall time through the custom-VJP Pallas kernels
    da_spec = OpSpec("matmul_dgrad", (256, 512, 256), "float32")
    _, da_derived = tuned_vs_default(da_spec, DEFAULT_MATMUL_TILES)
    db_spec = OpSpec("matmul_dgrad", (512, 256, 256), "float32")
    _, db_derived = tuned_vs_default(db_spec, DEFAULT_MATMUL_TILES)
    grad_fn = jax.grad(
        lambda a, b: jnp.sum(ops.matmul(a, b, interpret=True) ** 2),
        argnums=(0, 1))
    # backward stays float32 whatever --dtype drives the forward section
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    us, _ = timed(lambda: jax.tree.map(np.asarray, grad_fn(af, bf)))
    emit("kernel/matmul_256x512x256_bwd", us,
         f"dA {da_derived}; dB {db_derived}")

    # conv
    x = jnp.asarray(rng.normal(size=(1, 28, 28, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 32, 64)), jnp.float32)
    us, out = timed(lambda: np.asarray(
        ops.conv2d(x, w, tiles=DEFAULT_CONV_TILES, interpret=True)))
    np.testing.assert_allclose(out, ref.conv2d_ref(x, w), rtol=1e-2,
                               atol=1e-2)
    emit("kernel/conv_28x28x32x64", us, "allclose-vs-oracle OK")

    conv_spec = OpSpec("conv2d", (26, 26, 32, 64, 3, 3), "float32")
    cv_tiles, derived = tuned_vs_default(conv_spec, DEFAULT_CONV_TILES)
    us, tuned_out = timed(lambda: np.asarray(
        ops.conv2d(x, w, tiles=cv_tiles, interpret=True)))
    np.testing.assert_allclose(tuned_out, ref.conv2d_ref(x, w), rtol=1e-2,
                               atol=1e-2)
    emit("kernel/conv_28x28x32x64_tuned", us, derived)

    # conv BACKWARD: wgrad shares the forward dims; dgrad is the
    # transposed conv (28x28 output space, channels swapped)
    wg_spec = OpSpec("conv2d_wgrad", (26, 26, 32, 64, 3, 3), "float32")
    _, wg_derived = tuned_vs_default(wg_spec, DEFAULT_CONV_TILES)
    dg_spec = OpSpec("conv2d_dgrad", (28, 28, 64, 32, 3, 3), "float32")
    _, dg_derived = tuned_vs_default(dg_spec, DEFAULT_CONV_DGRAD_TILES)
    conv_grad = jax.grad(
        lambda x, w: jnp.sum(ops.conv2d(x, w, interpret=True) ** 2),
        argnums=(0, 1))
    us, _ = timed(lambda: jax.tree.map(np.asarray, conv_grad(x, w)))
    emit("kernel/conv_28x28x32x64_bwd", us,
         f"wgrad {wg_derived}; dgrad {dg_derived}")

    # attention
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    us, out = timed(lambda: np.asarray(
        ops.attention(q, k, v, tiles=(32, 32), interpret=True)))
    emit("kernel/flash_attn_128", us, "GQA causal OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="activation dtype for the forward-GEMM "
                         "tuned-vs-default comparisons, incl. the "
                         "quantized matmul_w8 variant (int8 weight "
                         "stream either way); the conv/backward/"
                         "attention sections stay float32")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast lane: the fused section only, at "
                         "reduced shapes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every record as machine-readable "
                         "JSON (the BENCH_kernels.json trajectory file)")
    args = ap.parse_args()
    if not args.smoke:
        run(dtype=args.dtype)
    run_fused(dtype=args.dtype, smoke=args.smoke)
    if args.json:
        write_json(args.json)


if __name__ == "__main__":
    main()
