"""Quantization subsystem: int8/fp8 representations, quantized kernels
vs their fake-quant oracles, dtype-aware schedules, and fp8/w8 serving."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.quant import (AbsMaxCalibrator, QuantizedTensor,
                         dequantize_params, fake_quant, logit_report,
                         quantize, quantize_params, quantized_bytes)


def _cfg(arch: str):
    return dataclasses.replace(get_reduced(arch), dtype=jnp.float32)


# ===================== representations & round trips ========================


def test_quantize_int8_per_channel_error_bound():
    """|fake_quant(x) - x| <= scale/2 per output channel (round-to-
    nearest with absmax scales)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)) * 3.0, jnp.float32)
    qt = quantize(x, "int8")
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (1, 32)
    err = np.abs(np.asarray(qt.dequant()) - np.asarray(x))
    bound = 0.5 * np.asarray(qt.scale) + 1e-6
    assert (err <= bound).all()


def test_quantize_fp8_and_per_tensor():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    qt = quantize(x, "fp8")
    assert qt.q.dtype == jnp.float8_e4m3fn
    # e4m3 has ~2 decimal digits: relative error well under 10%
    np.testing.assert_allclose(np.asarray(qt.dequant()), np.asarray(x),
                               rtol=0.1, atol=1e-3)
    pt = quantize(x, "int8", reduce_axis=None)
    assert np.asarray(pt.scale).size == 1
    fq = fake_quant(x, "int8", reduce_axis=None)
    assert fq.dtype == x.dtype
    with pytest.raises(ValueError):
        quantize(x, "int4")


def test_quantized_tensor_is_a_pytree():
    """jit / scan must treat QuantizedTensor like any other leaf pair —
    that is what lets quantized params drop into the engines unchanged."""
    rng = np.random.default_rng(2)
    stacked = jnp.asarray(rng.normal(size=(3, 8, 4)), jnp.float32)
    qt = quantize(stacked, "int8")            # (3, 1, 4) per-group scales
    assert qt.scale.shape == (3, 1, 4)

    def body(carry, w):                       # w: sliced QuantizedTensor
        assert isinstance(w, QuantizedTensor)
        return carry, w.dequant()

    _, deq = jax.lax.scan(body, 0.0, qt)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(qt.dequant()),
                               rtol=1e-6, atol=1e-6)
    out = jax.jit(lambda q: q.dequant().sum())(qt)
    assert np.isfinite(float(out))


def test_calibrator_absmax_and_ema():
    cal = AbsMaxCalibrator()
    cal.observe({"h": jnp.asarray([1.0, -2.0])})
    cal.observe({"h": jnp.asarray([0.5, 4.0])})
    s = cal.scales("int8")
    np.testing.assert_allclose(float(s["h"]), 4.0 / 127.0, rtol=1e-5)
    ema = AbsMaxCalibrator(momentum=0.5)
    ema.observe({"h": jnp.asarray([2.0])})
    ema.observe({"h": jnp.asarray([4.0])})
    np.testing.assert_allclose(float(ema.scales("int8")["h"]),
                               3.0 / 127.0, rtol=1e-5)
    with pytest.raises(ValueError):
        AbsMaxCalibrator(momentum=1.5)
    with pytest.raises(ValueError):
        AbsMaxCalibrator().scales()


# ========================= quantized kernels ================================


@pytest.mark.parametrize("per_channel", [True, False])
def test_matmul_w8_kernel_matches_oracle(per_channel):
    from repro.kernels import ops
    from repro.kernels.matmul_q import matmul_w8_ref
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    w_q = jnp.asarray(rng.integers(-127, 128, size=(64, 48)), jnp.int8)
    scale = (jnp.asarray(rng.uniform(0.01, 0.1, size=(48,)), jnp.float32)
             if per_channel else jnp.float32(0.02))
    out = ops.matmul_w8(a, w_q, scale, tiles=(8, 16, 16), interpret=True)
    ref = matmul_w8_ref(a, w_q, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_matmul_w8_ragged_falls_back_to_oracle():
    from repro.kernels import ops
    from repro.kernels.matmul_q import matmul_w8_ref
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(30, 64)), jnp.float32)   # 30 % 8 != 0
    w_q = jnp.asarray(rng.integers(-127, 128, size=(64, 48)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(48,)), jnp.float32)
    out = ops.matmul_w8(a, w_q, scale, tiles=(8, 16, 16), interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_w8_ref(a, w_q, scale)),
                               rtol=1e-5, atol=1e-5)


def test_quantized_linear_matches_fake_quant_reference():
    """ops.linear on a QuantizedTensor == x @ dequant(w), on both the
    dequant path and the blocked matmul_w8 kernel path."""
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)) * 0.1, jnp.float32)
    qt = quantize(w, "int8")
    ref = x @ qt.dequant(jnp.float32)
    out = ops.linear(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with ops.blocked_linear():                # kernel path (interpret)
        out_k = ops.linear(x, qt)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window,logit_cap", [(None, None), (7, None),
                                              (None, 30.0)])
def test_flash_decode_fp8_kernel_matches_oracle(window, logit_cap):
    """fp8-page Pallas kernel (interpret) == fp32-dequant dense oracle
    over ragged lengths, shuffled block tables and per-head scales."""
    from repro.kernels.flash_decode import (flash_decode_fp8,
                                            page_pool_shape,
                                            paged_attention_fp8_ref)
    rng = np.random.default_rng(6)
    B, hkv, G, D, page, nb = 3, 2, 3, 16, 8, 4
    n_pages = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, hkv, G, D)), jnp.float32)
    pool = page_pool_shape(n_pages, hkv, page, D)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float8_e4m3fn)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float8_e4m3fn)
    ks = jnp.asarray(rng.uniform(0.5, 2.0, size=(hkv,)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.5, 2.0, size=(hkv,)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    lengths = jnp.asarray([1, 13, 32], jnp.int32)
    out = flash_decode_fp8(q, kp, vp, ks, vs, bt, lengths, window=window,
                           logit_cap=logit_cap, interpret=True)
    ref = paged_attention_fp8_ref(q, kp, vp, ks, vs, bt, lengths,
                                  window=window, logit_cap=logit_cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_routes_fp8_pools():
    """ops.paged_attention on a 1-byte pool: unit-scale kernel output ==
    the plain oracle on cast pages (the dense-path fp8 semantics)."""
    from repro.kernels import ops
    from repro.kernels.flash_decode import (page_pool_shape,
                                            paged_attention_ref)
    rng = np.random.default_rng(7)
    B, hkv, G, D, page, nb = 2, 2, 2, 8, 4, 3
    q = jnp.asarray(rng.normal(size=(B, hkv * G, D)), jnp.float32)
    pool = page_pool_shape(B * nb + 1, hkv, page, D)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float8_e4m3fn)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float8_e4m3fn)
    bt = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    lengths = jnp.asarray([5, 11], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lengths, use_kernel=True,
                              interpret=True)
    ref = paged_attention_ref(q.reshape(B, hkv, G, D), kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(B, hkv * G, D)),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        wide = jnp.zeros(pool, jnp.float32)
        ops.paged_attention(q, wide, wide, bt, lengths,
                            k_scale=jnp.ones(hkv))


# ====================== quantized parameter trees ===========================


def test_quantize_params_tree_walk():
    """Projections quantize (incl. scan-stacked groups), norms /
    embeddings / MoE banks / recurrent mixers stay wide."""
    cfg = _cfg("recurrentgemma-9b")           # hybrid: attn + recurrent
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_params(params)
    stacked = qparams["layers"][0]
    found = []
    for g in qparams["layers"]:
        for key, leaf in g["mixer"].items():
            if isinstance(leaf, QuantizedTensor):
                found.append(key)
    assert "wq" in found and "wo" in found    # attention group quantized
    assert not any(isinstance(v, QuantizedTensor)
                   for g in qparams["layers"]
                   for v in g["norm1"].values())
    assert not isinstance(qparams["embed"]["embedding"], QuantizedTensor)
    # stacked weights carry per-(group, channel) scales
    wq = next(g["mixer"]["wq"] for g in qparams["layers"]
              if isinstance(g["mixer"].get("wq"), QuantizedTensor))
    assert wq.scale.shape == (wq.q.shape[0], 1, wq.q.shape[2])
    qb, db = quantized_bytes(qparams)
    assert qb < db                            # the containers save bytes

    moe = _cfg("phi3.5-moe-42b-a6.6b")
    mo_params = T.init_params(moe, jax.random.PRNGKey(0))
    mo_q = quantize_params(mo_params)
    ffn = mo_q["layers"][0]["ffn"]
    assert not any(isinstance(v, QuantizedTensor) for v in ffn.values())

    # round trip: dequantize_params restores a plain-array tree
    widened = dequantize_params(qparams, jnp.float32)
    assert not any(isinstance(x, QuantizedTensor)
                   for x in jax.tree.leaves(
                       widened,
                       is_leaf=lambda x: isinstance(x, QuantizedTensor)))


def test_quantized_model_tracks_fp_logits():
    """logit_report: w8 weights keep top-1 agreement on the reduced
    config — the fake-quant accuracy gate."""
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_params(params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, (2, 12)).astype(np.int32)
    rep = logit_report(cfg, params, qparams, tokens)
    assert rep["top1_agreement"] >= 0.9
    assert rep["rel_err"] < 0.05


# ======================== quantized serving path ============================


def test_w8_engine_matches_fake_quant_reference_tokens():
    """DecodeEngine with QuantizedTensor weights == the same engine on
    the dequantized (fake-quant) tree, token for token."""
    from repro.serve.engine import DecodeEngine, ServeConfig
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    qparams = quantize_params(params)
    fq = dequantize_params(qparams, jnp.float32)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, (2, 6)).astype(np.int32)
    ref = DecodeEngine(cfg, fq, ServeConfig(max_seq=24)).generate(
        prompts, 5)
    got = DecodeEngine(cfg, qparams, ServeConfig(max_seq=24)).generate(
        prompts, 5)
    np.testing.assert_array_equal(ref, got)


def test_fp8_paged_engine_token_exact_vs_fp8_dense():
    """Acceptance: fp8 paged decode (Pallas fp8 kernel forced on) stays
    token-exact against the fp8 dense path."""
    from repro.serve.engine import (DecodeEngine, PagedEngine,
                                    PagedServeConfig, ServeConfig)
    cfg = dataclasses.replace(_cfg("granite-3-8b"),
                              kv_cache_dtype=jnp.float8_e4m3fn)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 9)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    ref = [dense.generate(p[None, :], 6)[0] for p in prompts]
    paged = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=32, max_batch=2, page_size=8, decode_chunk=3,
        use_kernel=True, interpret=True))
    out = paged.generate(prompts, 6)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


def test_choose_page_size_uses_fp8_schedule_key(tmp_path):
    """An fp8 KV cache sizes its pages under "flash_decode_fp8" — a
    tuned fp8 entry must dictate the layout while the wide key's entry
    is ignored."""
    from repro.serve import kv_cache as KV
    from repro.tune import OpSpec, Schedule, ScheduleCache
    cfg = _cfg("granite-3-8b")
    g = cfg.n_heads // cfg.n_kv_heads
    cache = ScheduleCache(str(tmp_path / "schedules.json"))
    dims = (g, 64, cfg.head_dim)
    cache.store(Schedule(OpSpec("flash_decode", dims, "float32"), (16,),
                         source="measured"))
    cache.store(Schedule(OpSpec("flash_decode_fp8", dims, "float32"), (32,),
                         source="measured"))
    assert KV.choose_page_size(cfg, 64, cache=cache) == 16
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype=jnp.float8_e4m3fn)
    assert KV.choose_page_size(cfg8, 64, cache=cache) == 32


# ===================== kv_cache_dtype validation ============================


def test_kv_cache_dtype_validated_at_construction():
    cfg = _cfg("granite-3-8b")
    # the launch/dryrun.py --kv8 path: replace() must revalidate and pass
    ok = dataclasses.replace(cfg, kv_cache_dtype=jnp.float8_e4m3fn)
    assert jnp.dtype(ok.kv_cache_dtype).itemsize == 1
    for good in (jnp.float8_e5m2, jnp.bfloat16, jnp.float16, jnp.float32):
        dataclasses.replace(cfg, kv_cache_dtype=good)
    for bad in (jnp.int8, jnp.int32, jnp.float64, "not-a-dtype", object()):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            dataclasses.replace(cfg, kv_cache_dtype=bad)
