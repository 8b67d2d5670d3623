"""Quantization benchmark: modeled + measured wins vs the bf16 baseline.

Three axes (CSV contract ``name,us_per_call,derived``):

1. **w8 matmul** — the int8-weight GEMM under its dtype-aware schedule
   vs the bf16 GEMM under its own: modeled DRAM-boundary traffic in
   BYTES (per-operand widths through the paper's access model —
   ``tune.predicted_dram_bytes``) and measured interpret-mode wall time,
   with an allclose check against the fp32 fake-quant oracle.
2. **fp8 flash decode** — same comparison for the paged decode nest: the
   fp8 page pool streams at 1 byte/elem, and the fp8-aware search may
   pick a different page size than the bf16 one.
3. **decode tokens/sec** — PagedEngine end to end, quantized (int8
   weights + fp8 KV pool) vs the wide baseline on the same workload.

Wall-clock on CPU (Pallas interpret) is a machinery check, NOT a TPU
performance claim — the modeled byte ratios carry the hardware story
(docs/quantization.md).

    PYTHONPATH=src python -m benchmarks.quant_bench --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.configs import get_reduced
from repro.models import transformer as T
from repro.tune import OpSpec, best_schedule, predicted_dram_bytes


def bench_matmul_w8(dims: tuple[int, int, int]) -> None:
    from repro.kernels import ops
    from repro.kernels.matmul_q import matmul_w8_ref
    M, N, K = dims
    rng = np.random.default_rng(0)
    # measured and modeled agree on widths: bf16 activations both ways,
    # bf16 vs int8 weight stream
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.1, jnp.bfloat16)

    wide = best_schedule("matmul", (M, N, K), "bfloat16")
    narrow = best_schedule("matmul_w8", (M, N, K), "bfloat16")
    wide_bytes = predicted_dram_bytes(wide.spec, wide.tiles)
    narrow_bytes = predicted_dram_bytes(narrow.spec, narrow.tiles)

    from repro.quant import quantize
    qt = quantize(w.astype(jnp.float32), "int8")
    us_w, _ = timed(lambda: np.asarray(
        ops.matmul(a, w, tiles=wide.tiles, interpret=True)))
    us_q, out = timed(lambda: np.asarray(
        ops.matmul_w8(a, qt.q, qt.scale.reshape(-1), tiles=narrow.tiles,
                      interpret=True)))
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(matmul_w8_ref(a, qt.q, qt.scale.reshape(-1)),
                   np.float32),
        rtol=2e-2, atol=2e-2)
    emit(f"quant/matmul_w8_{M}x{N}x{K}", us_q,
         f"modeled DRAM {narrow_bytes:.3e}B vs bf16 {wide_bytes:.3e}B "
         f"({wide_bytes / max(narrow_bytes, 1):.2f}x reduction) "
         f"tiles {narrow.tiles} vs {wide.tiles}; measured "
         f"{us_w / max(us_q, 1e-9):.2f}x wall vs bf16 kernel; "
         "allclose-vs-oracle OK")


def bench_flash_decode_fp8(dims: tuple[int, int, int]) -> None:
    from repro.kernels.flash_decode import (flash_decode, flash_decode_fp8,
                                            page_pool_shape,
                                            paged_attention_fp8_ref)
    G, S, D = dims
    rng = np.random.default_rng(1)
    wide = best_schedule("flash_decode", (G, S, D), "bfloat16")
    narrow = best_schedule("flash_decode_fp8", (G, S, D), "bfloat16")
    wide_bytes = predicted_dram_bytes(wide.spec, wide.tiles)
    narrow_bytes = predicted_dram_bytes(narrow.spec, narrow.tiles)

    def make_pool(page, dtype):
        nb = -(-S // page)
        pool = page_pool_shape(nb + 1, 1, page, D)
        kp = jnp.asarray(rng.normal(size=pool), dtype)
        vp = jnp.asarray(rng.normal(size=pool), dtype)
        bt = jnp.asarray(1 + rng.permutation(nb)[None, :], jnp.int32)
        return kp, vp, bt

    # measured matches modeled: the baseline pool streams bf16 pages,
    # the quantized pool fp8 pages; q rides at bf16 in both
    q = jnp.asarray(rng.normal(size=(1, 1, G, D)), jnp.bfloat16)
    lengths = jnp.asarray([S], jnp.int32)
    ones = jnp.ones(1, jnp.float32)

    kp, vp, bt = make_pool(wide.tiles[0], jnp.bfloat16)
    us_w, _ = timed(lambda: np.asarray(
        flash_decode(q, kp, vp, bt, lengths, interpret=True)))
    kp8, vp8, bt8 = make_pool(narrow.tiles[0], jnp.float8_e4m3fn)
    us_q, out = timed(lambda: np.asarray(
        flash_decode_fp8(q, kp8, vp8, ones, ones, bt8, lengths,
                         interpret=True)))
    ref = paged_attention_fp8_ref(q, kp8, vp8, ones, ones, bt8, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    emit(f"quant/flash_decode_fp8_g{G}s{S}d{D}", us_q,
         f"modeled DRAM {narrow_bytes:.3e}B vs bf16 {wide_bytes:.3e}B "
         f"({wide_bytes / max(narrow_bytes, 1):.2f}x reduction) "
         f"page {narrow.tiles[0]} vs {wide.tiles[0]}; measured "
         f"{us_w / max(us_q, 1e-9):.2f}x wall vs bf16 kernel; "
         "allclose-vs-oracle OK")


def bench_decode_tps(arch: str, smoke: bool) -> None:
    from repro.quant import quantize_params, quantized_bytes
    from repro.serve.engine import PagedEngine, PagedServeConfig
    cfg = dataclasses.replace(get_reduced(arch), dtype=jnp.float32)
    if not smoke:
        cfg = dataclasses.replace(cfg, d_model=256, n_layers=4,
                                  n_heads=8, n_kv_heads=4, d_ff=1024,
                                  vocab=4096)
    n_req, gen, max_seq, slots = (4, 6, 32, 2) if smoke else (12, 48, 128, 4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (int(L),), dtype=np.int32)
               for L in rng.integers(4, 12, n_req)]
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype=jnp.float8_e4m3fn)
    qparams = quantize_params(params)
    qb, db = quantized_bytes(qparams)

    def tps(c, p):
        eng = PagedEngine(c, p, PagedServeConfig(max_seq=max_seq,
                                                 max_batch=slots))
        eng.generate(prompts, gen)             # warm the compile caches
        eng2 = PagedEngine(c, p, PagedServeConfig(max_seq=max_seq,
                                                  max_batch=slots))
        t0 = time.perf_counter()
        eng2.generate(prompts, gen)
        return n_req * gen / (time.perf_counter() - t0), eng2.page_size

    base_tps, base_page = tps(cfg, params)
    q_tps, q_page = tps(cfg8, qparams)
    emit("quant/decode_tps", 1e6 / max(q_tps, 1e-9),
         f"w8+fp8kv {q_tps:.1f} tok/s (page {q_page}) vs baseline "
         f"{base_tps:.1f} tok/s (page {base_page}) = "
         f"{q_tps / max(base_tps, 1e-9):.2f}x; projection weights "
         f"{qb / 1e6:.1f}MB vs bf16 {db / 1e6:.1f}MB")


def run(smoke: bool = False) -> None:
    if smoke:
        bench_matmul_w8((128, 128, 256))
        bench_flash_decode_fp8((4, 256, 64))
    else:
        bench_matmul_w8((512, 512, 1024))
        bench_flash_decode_fp8((8, 2048, 128))
    bench_decode_tps("granite-3-8b", smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + workload for CI")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
