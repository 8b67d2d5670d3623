"""Serving subsystem: paged KV cache, flash-decode kernel, scheduler,
and end-to-end continuous batching vs the dense static-batch engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.serve import kv_cache as KV
from repro.serve.engine import (DecodeEngine, PagedEngine, PagedServeConfig,
                                ServeConfig, default_buckets)
from repro.serve.scheduler import Request, Scheduler


def _cfg(arch: str):
    return dataclasses.replace(get_reduced(arch), dtype=jnp.float32)


# ===================== flash_decode kernel vs jnp oracle ====================


@pytest.mark.parametrize("window,logit_cap", [(None, None), (7, None),
                                              (None, 30.0), (5, 20.0)])
def test_flash_decode_kernel_matches_oracle(window, logit_cap):
    """Pallas kernel (interpret) == dense oracle over ragged cache
    lengths, shuffled block tables, GQA groups, partial last pages."""
    from repro.kernels.flash_decode import (flash_decode, page_pool_shape,
                                            paged_attention_ref)
    rng = np.random.default_rng(0)
    B, hkv, G, D, page, nb = 3, 2, 3, 16, 8, 4
    n_pages = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, hkv, G, D)), jnp.float32)
    pool = page_pool_shape(n_pages, hkv, page, D)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    lengths = jnp.asarray([1, 13, 32], jnp.int32)   # ragged, incl. edges
    out_k = flash_decode(q, kp, vp, bt, lengths, window=window,
                         logit_cap=logit_cap, interpret=True)
    out_r = paged_attention_ref(q, kp, vp, bt, lengths, window=window,
                                logit_cap=logit_cap)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


# ================ pools in place: aliased writer, layer index ================


def _pools(rng, dtype, shape):
    def one():
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)
    return one(), one()


@pytest.mark.parametrize("dtype,stacked,B,S,page,nb,pos,max_seq", [
    # one token per slot; slots 1 and 3 inactive: their block-table rows
    # are masked to the scratch page and their lengths to 0
    (jnp.bfloat16, True, 4, 1, 32, 2, [5, 0, 63, 0], None),
    # a span crossing 16-row tiles and a page boundary, partial at both
    # ends, beside a one-page-aligned span
    (jnp.bfloat16, True, 2, 40, 32, 4, [21, 64], None),
    # a span running past max_seq (the padded tail of a last chunk)
    (jnp.bfloat16, False, 2, 12, 32, 2, [58, 3], 64),
    # fp8 pools: 32-row tiles
    (jnp.float8_e4m3fn, True, 2, 40, 64, 3, [5, 150], None),
    (jnp.float8_e4m3fn, False, 3, 1, 64, 2, [0, 77, 127], None),
    # fp32 pools with pages smaller than a tile
    (jnp.float32, False, 3, 3, 4, 4, [0, 6, 14], None),
], ids=["bf16-decode-inactive", "bf16-span-tiles-pages",
        "bf16-span-past-max-seq", "fp8-span", "fp8-decode",
        "f32-small-pages"])
def test_kv_writer_matches_scatter_oracle(dtype, stacked, B, S, page, nb,
                                          pos, max_seq):
    """The aliased Pallas writer (interpret mode) leaves every real page
    bit-identical to the ``.at[].set`` oracle; only the scratch page,
    which no request reads, may differ."""
    from repro.kernels import ops
    rng = np.random.default_rng(7)
    hkv, D, layers = 2, 128, 3
    n_pages = B * nb + 1
    shape = ((layers,) if stacked else ()) + (n_pages, hkv, page, D)
    kp, vp = _pools(rng, dtype, shape)
    k = jnp.asarray(rng.normal(size=(B, S, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, hkv, D)), jnp.float32)
    bt = np.asarray(1 + rng.permutation(B * nb).reshape(B, nb), np.int32)
    pos = np.asarray(pos, np.int32)
    if S == 1 and B == 4:
        bt[[1, 3]] = KV.SCRATCH_PAGE
    layer = 1 if stacked else None
    args = (kp, vp, k, v, jnp.asarray(bt), jnp.asarray(pos), layer)
    got = ops.write_kv(*args, max_seq=max_seq, use_kernel=True,
                       interpret=True)
    want = ops.write_kv(*args, max_seq=max_seq, use_kernel=False)
    real = (slice(None), slice(1, None)) if stacked else (slice(1, None),)
    for g, w, old in zip(got, want, (kp, vp)):
        g, w = (np.asarray(x.astype(jnp.float32)) for x in (g, w))
        np.testing.assert_array_equal(g[real], w[real])
        assert not np.array_equal(g[real], np.asarray(
            old.astype(jnp.float32))[real])      # something was written


@pytest.mark.parametrize("kernel", ["flash_decode", "flash_decode_fp8",
                                    "flash_decode_oproj"])
def test_flash_decode_reads_stacked_pools_by_layer(kernel):
    """Each decode kernel over stacked pools with a layer index returns
    bit-for-bit what it returns over that layer's pool alone, and its
    oracle does the same."""
    from repro.kernels import flash_decode as FD
    rng = np.random.default_rng(8)
    B, hkv, G, D, page, nb, layers, E = 3, 2, 3, 16, 8, 4, 3, 24
    fp8 = kernel == "flash_decode_fp8"
    dtype = jnp.float8_e4m3fn if fp8 else jnp.float32
    kp, vp = _pools(rng, dtype, (layers, B * nb + 1, hkv, page, D))
    q = jnp.asarray(rng.normal(size=(B, hkv, G, D)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    lengths = jnp.asarray([1, 13, 32], jnp.int32)
    if kernel == "flash_decode_oproj":
        wo = jnp.asarray(rng.normal(size=(hkv, G * D, E)), jnp.float32)
        run = lambda k, v, *lay: FD.flash_decode_oproj(
            q, k, v, bt, lengths, wo, *lay, interpret=True)
        ref = lambda k, v, *lay: FD.paged_attention_oproj_ref(
            q, k, v, bt, lengths, wo, *lay)
    elif fp8:
        ks = jnp.asarray([0.5, 2.0], jnp.float32)
        vs = jnp.asarray([1.5, 0.25], jnp.float32)
        run = lambda k, v, *lay: FD.flash_decode_fp8(
            q, k, v, ks, vs, bt, lengths, *lay, interpret=True)
        ref = lambda k, v, *lay: FD.paged_attention_fp8_ref(
            q, k, v, ks, vs, bt, lengths, *lay)
    else:
        run = lambda k, v, *lay: FD.flash_decode(
            q, k, v, bt, lengths, *lay, window=5, interpret=True)
        ref = lambda k, v, *lay: FD.paged_attention_ref(
            q, k, v, bt, lengths, *lay, window=5)
    for layer in range(layers):
        for fn in (run, ref):
            np.testing.assert_array_equal(
                np.asarray(fn(kp, vp, jnp.int32(layer))),
                np.asarray(fn(kp[layer], vp[layer])))


@pytest.mark.parametrize("arch,n_layers,kw", [
    ("gemma2-9b", 5, {}),
    ("gemma2-9b", 5, {"spec_decode": 2}),
    ("gemma2-9b", 5, {"fuse": True}),
    ("recurrentgemma-9b", 7, {}),
], ids=["local-global-tail", "spec-decode", "fused", "hybrid-tail"])
def test_paged_engine_kernels_keep_pools_in_place(arch, n_layers, kw):
    """The engine with the Pallas writer and layer-indexed flash_decode
    forced on (interpret mode) serves the dense engine's greedy tokens,
    token for token: stacked pools written and read in place, tail layers
    on their own 4-D pools, recurrent states sliced per layer, chunked
    prefill and speculative verify through the span writer.  Random
    weights make greedy tokens blind to most attention faults, so the
    caches it leaves must also match the jnp oracle engine's: the K/V of
    every layer above the first is a function of the attention below."""
    cfg = dataclasses.replace(_cfg(arch), n_layers=n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 19)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    ref = [dense.generate(p[None, :], 6)[0] for p in prompts]
    caches = []
    for use_kernel in (True, False):
        paged = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=32, max_batch=2, page_size=8, decode_chunk=3,
            prefill_chunk=8, use_kernel=use_kernel, interpret=True, **kw))
        out = paged.generate(prompts, 6)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        caches.append(paged.cache)
    kernel_leaves = jax.tree_util.tree_flatten_with_path(caches[0])[0]
    for (path, got), want in zip(kernel_leaves, jax.tree.leaves(caches[1])):
        name = jax.tree_util.keystr(path)
        got, want = np.asarray(got), np.asarray(want)
        if "pages" in name:      # the scratch page holds no request's K/V
            real = (slice(None),) * (got.ndim - 4) + (slice(1, None),)
            got, want = got[real], want[real]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_paged_attention_matches_dense_attention_decode():
    """ops.paged_attention == layers.attention_decode on the same cache
    content (the paged layout is a pure re-indexing of the dense one)."""
    from repro.kernels import ops
    from repro.models import layers as L
    cfg = _cfg("granite-3-8b")
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(1)
    B, page, nb = 2, 4, 4
    max_seq = page * nb
    pos = 9                          # tokens 0..9 cached, 9 = current
    k_dense = jnp.asarray(rng.normal(size=(B, max_seq, hkv, hd)),
                          jnp.float32)
    v_dense = jnp.asarray(rng.normal(size=(B, max_seq, hkv, hd)),
                          jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.float32)

    # dense: softmax over slots <= pos
    groups = hq // hkv
    qh = q.reshape(B, hkv, groups, hd)
    logits = jnp.einsum("bhgd,blhd->bhgl", qh, k_dense) * hd ** -0.5
    valid = jnp.arange(max_seq) <= pos
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhgl,blhd->bhgd", probs, v_dense).reshape(B, hq, hd)

    # paged: same content scattered to (shuffled) pages per request
    n_pages = B * nb + 1
    kp = jnp.zeros((n_pages, hkv, page, hd), jnp.float32)
    vp = jnp.zeros((n_pages, hkv, page, hd), jnp.float32)
    bt = np.zeros((B, nb), np.int32)
    perm = 1 + rng.permutation(B * nb)
    for b in range(B):
        for i in range(nb):
            pg = int(perm[b * nb + i])
            bt[b, i] = pg
            kp = kp.at[pg].set(
                k_dense[b, i * page:(i + 1) * page].swapaxes(0, 1))
            vp = vp.at[pg].set(
                v_dense[b, i * page:(i + 1) * page].swapaxes(0, 1))
    lengths = jnp.full((B,), pos + 1, jnp.int32)
    out = ops.paged_attention(q, kp, vp, jnp.asarray(bt), lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ==================== paged vs dense logit equivalence ======================


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma2-9b",
                                  "recurrentgemma-9b"])
def test_paged_decode_logits_match_dense(arch):
    """prefill -> N decode steps: the paged cache + flash-decode path
    must reproduce the dense ring-buffer decode logits."""
    cfg = _cfg(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    L, steps, page, max_seq = 6, 5, 4, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, L + steps)),
                       jnp.int32)

    log_d, cache_d = T.prefill(cfg, params, toks[:, :L], max_seq)

    nb = KV.num_blocks(max_seq, page)
    paged = KV.init_paged_cache(cfg, batch=1, n_pages=nb + 1,
                                page_size=page)
    pages = jnp.arange(1, nb + 1, dtype=jnp.int32)
    log_p, dense_full = T.prefill(cfg, params, toks[:, :L], max_seq,
                                  full_kv=True, logits_at=L - 1)
    paged = KV.write_prefill(cfg, paged, dense_full, jnp.int32(0), pages,
                             page)
    block_tables = pages[None, :]
    np.testing.assert_allclose(np.asarray(log_p), np.asarray(log_d),
                               rtol=1e-5, atol=1e-4)

    lengths = jnp.asarray([L], jnp.int32)
    for t in range(L, L + steps):
        log_d, cache_d = T.decode_step(cfg, params, toks[:, t], cache_d,
                                       jnp.int32(t))
        attn = KV.make_paged_attn_step(cfg, block_tables)
        log_p, paged = T.decode_step(cfg, params, toks[:, t], paged,
                                     lengths, attn_step=attn)
        lengths = lengths + 1
        np.testing.assert_allclose(np.asarray(log_p), np.asarray(log_d),
                                   rtol=1e-5, atol=1e-4, err_msg=str(t))


# ========================= scheduler invariants =============================


def test_allocator_basics():
    a = KV.PageAllocator(5)
    assert a.capacity == 4 and a.available() == 4
    p = a.alloc()
    assert p != KV.SCRATCH_PAGE
    a.share(p)
    a.free(p)
    assert a.available() == 3        # still one reference held
    a.free(p)
    assert a.available() == 4
    with pytest.raises(ValueError):
        a.free(p)                    # double free
    pages = a.alloc_many(4)
    with pytest.raises(MemoryError):
        a.alloc()
    a.free_many(pages)
    assert a.available() == 4


def test_scheduler_rejects_oversized_request():
    sched = Scheduler(2, 4, KV.PageAllocator(9), max_seq=16)
    with pytest.raises(ValueError):
        sched.submit(Request(0, np.zeros(10, np.int32), 10))


def test_scheduler_rejects_request_exceeding_pool_capacity():
    """A request needing more pages than the whole pool would never be
    admitted — submit must fail loudly instead of spinning forever."""
    sched = Scheduler(2, 8, KV.PageAllocator(3), max_seq=64)
    with pytest.raises(ValueError, match="pool"):
        sched.submit(Request(0, np.zeros(20, np.int32), 8))


def test_scheduler_invariants_hypothesis():
    """Random submit/step/evict traces: no page leaked or double-owned,
    capacity never exceeded, FIFO admission under the page budget."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(data=st.data())
    def run(data):
        n_pages = data.draw(st.integers(3, 12))
        page_size = data.draw(st.sampled_from([2, 4, 8]))
        max_batch = data.draw(st.integers(1, 4))
        max_seq = page_size * (n_pages - 1)
        alloc = KV.PageAllocator(n_pages)
        sched = Scheduler(max_batch, page_size, alloc, max_seq)
        rid = 0
        for _ in range(data.draw(st.integers(1, 12))):
            op = data.draw(st.sampled_from(["submit", "admit", "finish"]))
            if op == "submit":
                L = data.draw(st.integers(1, max(1, max_seq // 2)))
                n = data.draw(st.integers(1, max(1, max_seq - L)))
                sched.submit(Request(rid, np.zeros(L, np.int32), n))
                rid += 1
            elif op == "admit":
                for req in sched.admit():
                    assert req.slot >= 0
                    assert len(req.pages) == sched.pages_needed(req)
            elif sched.running:
                slot = data.draw(st.sampled_from(
                    sorted(sched.running)))
                sched.evict(slot)
            # -- invariants ----------------------------------------------
            owned = [p for r in sched.running.values() for p in r.pages]
            assert len(owned) == len(set(owned)), "page double-owned"
            assert KV.SCRATCH_PAGE not in owned, "scratch page owned"
            assert alloc.in_use() == len(owned), "page leak"
            assert alloc.available() >= 0
            assert len(sched.running) <= max_batch
        # drain: every page returns
        for slot in sorted(sched.running):
            sched.evict(slot)
        assert alloc.available() == alloc.capacity

    run()


# =========================== end-to-end engines =============================


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma2-9b",
                                  "recurrentgemma-9b", "mamba2-780m"])
def test_paged_generate_matches_dense_engine(arch):
    """Greedy continuous batching == token-for-token the dense engine,
    with ragged prompts, more requests than slots (forced eviction +
    re-admission), and a mid-stream slot reuse."""
    cfg = _cfg(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 9, 12)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=64))
    ref = [dense.generate(p[None, :], 10)[0] for p in prompts]
    paged = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, page_size=8, decode_chunk=4))
    out = paged.generate(prompts, 10)
    for i, (a, b) in enumerate(zip(ref, out)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_paged_engine_flash_decode_kernel_path():
    """Same equivalence with the Pallas flash-decode kernel forced on
    (interpret mode) — the acceptance path of the subsystem."""
    cfg = _cfg("granite-3-8b")
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


def test_dense_engine_scan_generate_single_transfer():
    """The static engine's token loop is one device program: generate
    must produce identical tokens across calls and batch sizes."""
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (3, 8)).astype(np.int32)
    eng = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    out = eng.generate(prompts, 7)
    assert out.shape == (3, 7)
    # batch-invariance: each row alone reproduces its batched tokens
    for b in range(3):
        np.testing.assert_array_equal(
            eng.generate(prompts[b:b + 1], 7)[0], out[b])


def test_temperature_sampling_stays_in_vocab():
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (6,)).astype(np.int32)]
    paged = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=32, max_batch=1, page_size=8, temperature=0.8))
    out = paged.generate(prompts, 8)
    assert out.shape == (1, 8)
    assert (out >= 0).all() and (out < cfg.vocab).all()


# =========================== paged-cache pieces =============================


def test_choose_page_size_uses_schedule_cache(tmp_path):
    """A tuned flash_decode entry must dictate the paged layout."""
    from repro.tune import OpSpec, Schedule, ScheduleCache
    cfg = _cfg("granite-3-8b")
    g = cfg.n_heads // cfg.n_kv_heads
    cache = ScheduleCache(str(tmp_path / "schedules.json"))
    spec = OpSpec("flash_decode", (g, 64, cfg.head_dim), "float32")
    cache.store(Schedule(spec, (16,), source="measured"))
    assert KV.choose_page_size(cfg, 64, cache=cache) == 16


def test_default_buckets_policy():
    """Pure-attention stacks bucket to powers of two; recurrent/SSD
    stacks prefill at exact lengths (right-padding would corrupt their
    O(1) states)."""
    attn = _cfg("granite-3-8b")
    assert default_buckets(attn, 64) is not None
    assert all(b2 % b1 == 0 for b1, b2 in
               zip(default_buckets(attn, 64), default_buckets(attn, 64)[1:]))
    hybrid = _cfg("recurrentgemma-9b")
    assert default_buckets(hybrid, 64) is None


def test_paged_cache_defs_reject_encdec():
    cfg = _cfg("seamless-m4t-medium")
    with pytest.raises(NotImplementedError):
        KV.paged_cache_defs(cfg, 1, 4, 4)


def test_shared_prefix_pages_are_read_only_safe():
    """Two requests sharing full prefix pages decode independently:
    refcounted pages stay intact until the last owner frees them."""
    a = KV.PageAllocator(6)
    prefix = a.alloc_many(2)
    shared = [a.share(p) for p in prefix]
    assert shared == prefix
    a.free_many(prefix)              # first owner done
    assert a.in_use() == 2           # second owner still holds them
    a.free_many(prefix)
    assert a.available() == a.capacity


# ====================== cross-op fusion e2e (ISSUE 5) =======================


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma2-9b",
                                  "recurrentgemma-9b", "mamba2-780m"])
def test_paged_generate_fused_matches_dense_engine(arch):
    """Token-exact paged-decode e2e with fusion enabled: the fused
    paged engine (epilogue-fused MLP, one-pass QKV, oproj-fused decode
    attention) reproduces the UNFUSED dense engine token for token
    across the arch families — fusion changes where tensors live, not
    what they are."""
    cfg = _cfg(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 9, 12)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=64))
    ref = [dense.generate(p[None, :], 10)[0] for p in prompts]
    fused = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, page_size=8, decode_chunk=4, fuse=True))
    out = fused.generate(prompts, 10)
    for i, (a, b) in enumerate(zip(ref, out)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_dense_engine_fused_matches_unfused():
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    ref = DecodeEngine(cfg, params,
                       ServeConfig(max_seq=32)).generate(prompts, 7)
    out = DecodeEngine(cfg, params,
                       ServeConfig(max_seq=32,
                                   fuse=True)).generate(prompts, 7)
    np.testing.assert_array_equal(ref, out)


def test_paged_engine_fused_kernel_path():
    """Fusion with the Pallas kernels forced on (interpret mode): the
    oproj-fused flash-decode runs inside the jitted decode chunk."""
    cfg = _cfg("granite-3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 9)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    ref = [dense.generate(p[None, :], 6)[0] for p in prompts]
    fused = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=32, max_batch=2, page_size=8, decode_chunk=3,
        use_kernel=True, interpret=True, fuse=True))
    out = fused.generate(prompts, 6)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


def test_fused_serving_composes_with_w8_quantization():
    """ISSUE 5 acceptance: serve --fuse composes with --quantize w8.

    Token-exact: the fused paged engine over int8 projection weights
    reproduces the fused DENSE engine over the same weights (both run
    the w8 epilogue-fused semantics).  Drift-bounded: fused-vs-unfused
    quantized logits differ only in scale-application order — (a@q)*s
    vs a@(q*s) — which must stay far inside the fake-quant harness
    tolerance."""
    from repro.quant import quantize_params
    cfg = _cfg("granite-3-8b")
    raw = T.init_params(cfg, jax.random.PRNGKey(2))
    params = quantize_params(raw)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 9)]
    dense_fused = DecodeEngine(cfg, params, ServeConfig(max_seq=32,
                                                        fuse=True))
    ref = [dense_fused.generate(p[None, :], 6)[0] for p in prompts]
    fused = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=32, max_batch=2, page_size=8, decode_chunk=3,
        fuse=True))
    out = fused.generate(prompts, 6)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)

    toks = jnp.asarray(prompts[1][None, :])
    from repro.kernels import ops as K_ops
    log_unfused, _ = T.prefill(cfg, params, toks, 32)
    with K_ops.fused_ops(True):
        log_fused, _ = T.prefill(cfg, params, toks, 32)
    np.testing.assert_allclose(np.asarray(log_fused),
                               np.asarray(log_unfused),
                               rtol=1e-4, atol=1e-4)


def test_fused_serving_composes_with_fp8_kv():
    """--fuse + an fp8 page pool: the oproj fusion falls back to the
    unfused fp8 decode pair inside ops.paged_attention_oproj, so the
    composition stays token-exact against the fp8 dense path."""
    import dataclasses as dc
    cfg = dc.replace(_cfg("granite-3-8b"),
                     kv_cache_dtype=jnp.float8_e4m3fn)
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 8)]
    dense = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    ref = [dense.generate(p[None, :], 5)[0] for p in prompts]
    fused = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=32, max_batch=2, page_size=8, decode_chunk=2,
        fuse=True))
    out = fused.generate(prompts, 5)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
