"""Cross-op fusion (ISSUE 5): the FusedProblem capacity model, the
epilogue-fused / weight-stationary / oproj-fused Pallas kernels vs
their unfused op chains, and the tune plumbing for the new op keys."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fusion import (Epilogue, FusedProblem, fused_energy_pj,
                               fused_multicore_dram_bytes, optimize_fused)
from repro.core.loopnest import Problem
from repro.kernels import ops
from repro.kernels.flash_decode import page_pool_shape

BUDGET = 2 * 1024 * 1024


# ========================= FusedProblem model ==============================


def test_fused_problem_validates_chain():
    p1 = Problem.gemm(M=64, N_cols=128, K_reduce=32)
    ok = Problem.gemm(M=64, N_cols=32, K_reduce=128)
    FusedProblem.pair(p1, ok)
    with pytest.raises(ValueError, match="consumes"):
        FusedProblem.pair(p1, Problem.gemm(M=64, N_cols=32, K_reduce=64))
    with pytest.raises(ValueError, match="row dim"):
        FusedProblem.pair(p1, Problem.gemm(M=32, N_cols=32, K_reduce=128))
    with pytest.raises(ValueError, match="at least two"):
        FusedProblem((p1,), (Epilogue(),))
    with pytest.raises(ValueError, match="GEMM-family"):
        FusedProblem.pair(Problem(X=8, Y=2, C=4, K=8), ok)


def test_tiles_must_share_fusion_dim_and_divide():
    fp = FusedProblem.mlp(M=64, d_model=32, d_ff=128)
    fp.validate_tiles([(16, 32, 64), (16, 128, 32)])
    with pytest.raises(ValueError, match="shared fusion tile"):
        fp.validate_tiles([(16, 32, 64), (32, 128, 32)])
    with pytest.raises(ValueError, match="divide"):
        fp.validate_tiles([(16, 32, 48), (16, 128, 32)])


def test_fused_never_exceeds_unfused_sweep():
    """Deterministic sweep of the core invariant: for any valid fusion
    tile the fused chain's predicted DRAM bytes never exceed the
    unfused pair's (a fused kernel can always spill the tile)."""
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    for bm in (8, 32, 64, 256):
        for bk in (32, 128):
            for bn in (64, 128):
                tiles = [(bm, bk, min(bn, 512)), (bm, min(bk, 512), bn)]
                tr = fp.traffic(tiles, BUDGET)
                assert tr.total_bytes <= tr.unfused_total_bytes, \
                    (tiles, tr)


def test_intermediate_zero_when_tile_fits():
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    tiles = [(64, 128, 128), (64, 512, 128)]
    assert fp.intermediate_fits(0, tiles, BUDGET)
    tr = fp.traffic(tiles, BUDGET, always_resident=True)
    assert tr.intermediate_resident == (True,)
    assert tr.intermediate_bytes == (0,)


def test_intermediate_counts_when_tile_does_not_fit():
    """A tiny level-0 budget spills the fusion tile: the intermediate
    crosses DRAM on both sides and the model says so."""
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    tiles = [(256, 128, 512), (256, 512, 128)]
    tiny = 4 * 1024
    assert not fp.intermediate_fits(0, tiles, tiny)
    tr = fp.traffic(tiles, tiny)
    assert tr.intermediate_resident == (False,)
    assert tr.intermediate_bytes[0] > 0
    # both sides: at least one write + one read of the full tensor
    assert tr.intermediate_bytes[0] >= \
        2 * fp.intermediate_elems(0) * fp.intermediate_bpe(0)


def test_epilogues_always_fuse():
    """Epilogue round-trips (activation, residual) are eliminated even
    when the inter-GEMM tile spills: fused < unfused at any budget."""
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    tiles = [(64, 128, 128), (64, 512, 128)]
    tiny = 4 * 1024
    tr = fp.traffic(tiles, tiny)
    assert tr.total_bytes < tr.unfused_total_bytes


def test_optimize_fused_reports_positive_savings():
    fp = FusedProblem.mlp(M=512, d_model=256, d_ff=1024)
    results = optimize_fused(fp, BUDGET)
    assert results, "search returned no feasible joint schedule"
    best = results[0]
    assert best.savings_bytes > 0
    assert best.fused_bytes == fp.fused_dram_bytes(best.tiles, BUDGET)
    # ranked: fused bytes non-decreasing
    fb = [r.fused_bytes for r in results]
    assert fb == sorted(fb)
    assert "saves" in best.summary()


def test_swiglu_and_w8_variants_model():
    """The SwiGLU gating multiply adds a streamed operand; the w8
    weight stream narrows — both flow through the model's per-operand
    byte accounting."""
    wide = FusedProblem.mlp(M=256, d_model=128, d_ff=512, swiglu=True)
    w8 = FusedProblem.mlp(M=256, d_model=128, d_ff=512, swiglu=True,
                          weight_bytes=1)
    tiles = [(64, 128, 128), (64, 512, 128)]
    assert w8.fused_dram_bytes(tiles, BUDGET) < \
        wide.fused_dram_bytes(tiles, BUDGET)


def test_fused_energy_below_unfused_stage_sum():
    from repro.core.hierarchy import MemLevel, energy_fixed
    from repro.core.fusion import _gemm_string
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    tiles = [(64, 128, 128), (64, 512, 128)]
    levels = [MemLevel.sram("VMEM", BUDGET), MemLevel.dram("HBM")]
    unfused = sum(energy_fixed(_gemm_string(p, t), levels).mem_pj
                  for p, t in zip(fp.stages, tiles))
    assert fused_energy_pj(fp, tiles, BUDGET) < unfused


def test_multicore_fusion_only_survives_xy_partitioning():
    """K partitioning scatters the intermediate's channels across cores
    while the consumer reduces over all of them — fusion buys nothing
    there; XY keeps the per-core fusion intact."""
    fp = FusedProblem.mlp(M=256, d_model=128, d_ff=512)
    tiles = [(64, 128, 128), (64, 512, 128)]
    single = fp.fused_dram_bytes(tiles, BUDGET)
    # XY at 1 core degenerates to the single-core fused chain
    assert fused_multicore_dram_bytes(fp, tiles, BUDGET, "XY", 1) == single
    # K scatters the intermediate's channels across cores: it is NEVER
    # eliminated, so the K-scheme chain carries strictly more traffic
    # than the single-core fused chain that kept it resident
    kk = fused_multicore_dram_bytes(fp, tiles, BUDGET, "K", 4)
    assert fp.traffic(tiles, BUDGET).intermediate_resident == (True,)
    assert kk > single
    with pytest.raises(ValueError):
        fused_multicore_dram_bytes(fp, tiles, BUDGET, "Z", 4)


def test_fusion_capacity_property_hypothesis():
    """ISSUE 5 satellite: for ANY valid fusion tile, predicted fused
    DRAM bytes <= the unfused pair's, and the intermediate contributes
    zero DRAM traffic when its tile fits level 0.  Stated on the
    capacity layer (FusedProblem), not on search winners."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dims = st.sampled_from([16, 32, 64, 128, 256])
    tile_of = st.sampled_from([8, 16, 32, 64, 128, 256])

    @settings(max_examples=60, deadline=None)
    @given(data=st.data())
    def run(data):
        M = data.draw(dims)
        d_model = data.draw(dims)
        d_ff = data.draw(dims)
        swiglu = data.draw(st.booleans())
        wb = data.draw(st.sampled_from([None, 1]))
        fp = FusedProblem.mlp(M, d_model, d_ff, swiglu=swiglu,
                              weight_bytes=wb)

        def tile(full):
            t = data.draw(tile_of)
            while full % t:
                t //= 2
            return max(t, 1)

        bm = tile(M)
        tiles = [(bm, tile(d_model), tile(d_ff)),
                 (bm, tile(d_ff), tile(d_model))]
        budget = data.draw(st.sampled_from(
            [8 * 1024, 64 * 1024, 1024 * 1024]))
        tr = fp.traffic(tiles, budget)
        assert tr.total_bytes <= tr.unfused_total_bytes
        if fp.intermediate_fits(0, tiles, budget):
            forced = fp.traffic(tiles, budget, always_resident=True)
            assert forced.intermediate_bytes == (0,)
            assert forced.total_bytes <= tr.unfused_total_bytes or \
                not forced.intermediate_resident[0]

    run()


# ===================== fused kernels vs unfused chains ======================


@pytest.mark.parametrize("kw", [
    {},
    {"act": "gelu", "bias": True},
    {"act": "silu", "mul": True},
    {"residual": True},
    {"act": "relu", "bias": True, "mul": True, "residual": True},
])
def test_matmul_fused_kernel_matches_unfused_chain(kw):
    """The epilogue-fused GEMM == the per-op chain (matmul, then bias,
    act, mul, residual as separate jnp ops) within fp tolerance."""
    rng = np.random.default_rng(0)
    M, K, N = 32, 64, 48
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32) \
        if kw.get("bias") else None
    mul = jnp.asarray(rng.normal(size=(M, N)), jnp.float32) \
        if kw.get("mul") else None
    res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32) \
        if kw.get("residual") else None
    act = kw.get("act", "none")

    chain = jnp.dot(a, w)
    if bias is not None:
        chain = chain + bias
    chain = {"none": lambda x: x, "relu": jax.nn.relu,
             "gelu": jax.nn.gelu, "silu": jax.nn.silu}[act](chain)
    if mul is not None:
        chain = chain * mul
    if res is not None:
        chain = chain + res

    out = ops.matmul_fused(a, w, bias=bias, act=act, mul=mul,
                           residual=res, tiles=(16, 32, 16),
                           use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(chain),
                               rtol=1e-5, atol=1e-5)


def test_matmul_fused_w8_matches_quantized_chain():
    """int8-weight epilogue fusion == dequant GEMM + the pointwise tail
    (the PR 4 path composes with fusion)."""
    from repro.kernels.matmul_q import matmul_w8_ref
    from repro.quant import quantize
    rng = np.random.default_rng(1)
    M, K, N = 32, 64, 48
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    qt = quantize(w, "int8")
    chain = jax.nn.gelu(matmul_w8_ref(a, qt.q, qt.scale.reshape(-1))) \
        + res
    out = ops.matmul_fused(a, qt, act="gelu", residual=res,
                           tiles=(16, 32, 16), use_kernel=True,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(chain),
                               rtol=1e-4, atol=1e-4)


def test_matmul_fused_ragged_falls_back_to_oracle():
    """Non-dividing shapes take the jnp oracle: identical to the
    unfused chain bit-for-bit in fp32."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(30, 52)), jnp.float32)  # ragged
    w = jnp.asarray(rng.normal(size=(52, 37)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(30, 37)), jnp.float32)
    out = ops.matmul_fused(a, w, act="gelu", residual=res,
                           use_kernel=True, interpret=True)
    chain = jax.nn.gelu(jnp.dot(a, w)) + res
    np.testing.assert_array_equal(np.asarray(out), np.asarray(chain))


def test_matmul_fused_strided_operands():
    """Transposed (strided) operand views hit the same kernel path and
    match the unfused chain — the layout is materialized by XLA, not
    assumed by the BlockSpecs."""
    rng = np.random.default_rng(7)
    at = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    wt = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
    a, w = at.T, wt.T                      # (32, 64) @ (64, 48)
    res = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    out = ops.matmul_fused(a, w, act="gelu", residual=res,
                           tiles=(16, 32, 16), use_kernel=True,
                           interpret=True)
    chain = jax.nn.gelu(jnp.dot(a, w)) + res
    np.testing.assert_allclose(np.asarray(out), np.asarray(chain),
                               rtol=1e-5, atol=1e-5)


def test_matmul_fused_leading_dims():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    out = ops.matmul_fused(x, w, residual=res, tiles=(8, 32, 16),
                           use_kernel=True, interpret=True)
    assert out.shape == (2, 16, 32)
    ref = jnp.einsum("bsk,kn->bsn", x, w) + res
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_qkv_fused_matches_three_gemms():
    rng = np.random.default_rng(4)
    M, K, nkv, g = 24, 64, 32, 3
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(K, g * nkv)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(K, nkv)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(K, nkv)), jnp.float32)
    q, k, v = ops.qkv_fused(x, wq, wk, wv, tiles=(8, 32, 16),
                            use_kernel=True, interpret=True)
    for got, w in ((q, wq), (k, wk), (v, wv)):
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(x @ w), rtol=1e-5,
                                   atol=1e-5)


def test_qkv_fused_ragged_oracle_is_exact():
    """Ragged / non-GQA-multiple shapes fall back to three dots that
    are bit-identical to the unfused projections in fp32."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 7, 48)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(48, 36)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(48, 12)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(48, 12)), jnp.float32)
    q, k, v = ops.qkv_fused(x, wq, wk, wv, use_kernel=True,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(x @ wq))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(x @ wk))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(x @ wv))


@pytest.mark.parametrize("window,logit_cap", [(None, None), (7, None),
                                              (None, 30.0), (5, 20.0)])
def test_flash_decode_oproj_matches_unfused_pair(window, logit_cap):
    """The oproj-fused decode kernel == paged attention followed by the
    dense projection, over ragged lengths and shuffled block tables."""
    rng = np.random.default_rng(6)
    B, hkv, G, D, page, nb, E = 3, 2, 3, 16, 8, 4, 40
    n_pages = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, hkv * G, D)), jnp.float32)
    pool = page_pool_shape(n_pages, hkv, page, D)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb),
                     jnp.int32)
    lengths = jnp.asarray([1, 13, 32], jnp.int32)
    wo = jnp.asarray(rng.normal(size=(hkv * G * D, E)), jnp.float32)

    unfused = ops.paged_attention(q, kp, vp, bt, lengths, window=window,
                                  logit_cap=logit_cap)
    unfused = unfused.reshape(B, hkv * G * D) @ wo

    fused = ops.paged_attention_oproj(q, kp, vp, bt, lengths, wo,
                                      window=window,
                                      logit_cap=logit_cap,
                                      use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-4, atol=1e-4)
    # the off-kernel oracle is the exact unfused pair
    oracle = ops.paged_attention_oproj(q, kp, vp, bt, lengths, wo,
                                       window=window,
                                       logit_cap=logit_cap,
                                       use_kernel=False)
    np.testing.assert_allclose(np.asarray(oracle), np.asarray(unfused),
                               rtol=1e-6, atol=1e-6)


# ====================== model-layer fusion routing ==========================


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma2-9b"])
def test_mlp_and_attention_fused_context_is_exact(arch):
    """With fused ops enabled (oracle path, as the engines run on CPU)
    the MLP block and attention are bit-identical to the unfused
    layers in fp32 — the invariant the token-exact serving tests
    lean on."""
    import dataclasses
    from repro.configs import get_reduced
    from repro.models import layers as L
    cfg = dataclasses.replace(get_reduced(arch), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    mdefs = L.mlp_defs(cfg, 1)
    from repro.models.base import build
    mp = build(mdefs, "init", key)
    ref_out = L.mlp_apply(mp, x, residual=h)
    with ops.fused_ops(True):
        fused_out = L.mlp_apply(mp, x, residual=h)
    np.testing.assert_array_equal(np.asarray(ref_out),
                                  np.asarray(fused_out))

    adefs = L.attention_defs(cfg, 1)
    ap = build(adefs, "init", key)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    ref_attn = L.attention_apply(cfg, ap, x, positions)
    with ops.fused_ops(True):
        fused_attn = L.attention_apply(cfg, ap, x, positions)
    np.testing.assert_array_equal(np.asarray(ref_attn),
                                  np.asarray(fused_attn))


def test_fused_ops_flag_default_off():
    assert not ops.fused_ops_enabled()
    with ops.fused_ops(True):
        assert ops.fused_ops_enabled()
        with ops.fused_ops(False):
            assert not ops.fused_ops_enabled()
    assert not ops.fused_ops_enabled()


# ========================= tune plumbing (new keys) =========================


@pytest.mark.parametrize("op,dims", [
    ("matmul_fused", (256, 512, 256)),
    ("qkv_fused", (64, 64, 256, 4)),
    ("flash_decode_oproj", (4, 512, 64, 256)),
])
def test_fused_op_schedules_divide_fit_and_round_trip(op, dims):
    from repro.tune import (OpSpec, Schedule, candidates, divides,
                            fits_vmem, predicted_dram_bytes, vmem_budget)
    spec = OpSpec(op, dims, "float32")
    ranked = candidates(spec)
    assert ranked
    budget = vmem_budget()
    for s in ranked:
        assert divides(spec, s.tiles), s
        assert fits_vmem(spec, s.tiles, budget), s
        assert predicted_dram_bytes(spec, s.tiles) > 0
    # JSON round trip through the schedule cache format
    rt = Schedule.from_json(ranked[0].to_json())
    assert rt.spec == spec and rt.tiles == ranked[0].tiles


def test_fused_op_schedule_cache_round_trip(tmp_path):
    from repro.tune import OpSpec, Schedule, ScheduleCache
    cache = ScheduleCache(str(tmp_path / "schedules.json"))
    spec = OpSpec("flash_decode_oproj", (2, 128, 32, 64), "float32")
    cache.store(Schedule(spec, (64,), source="measured",
                         measured_us=3.0), device="cpu")
    hit = ScheduleCache(str(tmp_path / "schedules.json")).lookup(
        spec, device="cpu")
    assert hit is not None and hit.tiles == (64,)


def test_choose_page_size_fused_key(tmp_path):
    """A fusion-enabled engine sizes its pages under the
    flash_decode_oproj key — a tuned entry there wins."""
    import dataclasses
    from repro.configs import get_reduced
    from repro.serve.kv_cache import choose_page_size
    from repro.tune import OpSpec, Schedule, ScheduleCache
    cfg = dataclasses.replace(get_reduced("granite-3-8b"),
                              dtype=jnp.float32)
    g = cfg.n_heads // cfg.n_kv_heads
    cache = ScheduleCache(str(tmp_path / "s.json"))
    spec = OpSpec("flash_decode_oproj",
                  (g, 64, cfg.head_dim, cfg.d_model), "float32")
    cache.store(Schedule(spec, (16,)), device="cpu")
    assert choose_page_size(cfg, 64, cache=cache, fused=True) == 16


def test_measure_runs_fused_ops():
    """The measurement harness executes all three fused op kinds end to
    end (interpret mode) without falling over."""
    from repro.tune import OpSpec, Schedule
    from repro.tune.measure import make_inputs, run_once
    for op, dims, tiles in [
        ("matmul_fused", (32, 32, 64), (16, 32, 16)),
        ("qkv_fused", (16, 16, 64, 2), (8, 32, 16)),
        ("flash_decode_oproj", (2, 64, 32, 64), (16,)),
    ]:
        sched = Schedule(OpSpec(op, dims, "float32"), tiles)
        out = run_once(sched, make_inputs(sched), interpret=True)
        assert np.all(np.isfinite(np.asarray(out, np.float32)))