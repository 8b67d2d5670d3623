"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode runs tiles that Mosaic refuses (a block that splits the
sublane or lane dim unaligned, a VMEM overrun), so every main-path
kernel is compiled here for a described ``v5e:2x2`` topology — no chip
attached, nothing runs — at granite-3-8b's published widths and the
tiles the blocking model picks for them.  The topology is described in
a fixture, only once a test of this file runs: the TPU runtime may be
loaded by one process at a time.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.granite_3_8b import CONFIG
from repro.kernels.flash_decode import page_pool_shape
from repro.serve import kv_cache as KV
from repro.tune import best_schedule

MAX_SEQ = 4096
SLOTS = 8               # the page pool holds SLOTS whole contexts
DECODE_SLOTS = 32       # request slots of a decode program
POOL_LAYERS = 4         # layers of the stacked pools in a step program
HKV, G, D, E, F = (CONFIG.n_kv_heads, CONFIG.n_heads // CONFIG.n_kv_heads,
                   CONFIG.head_dim, CONFIG.d_model, CONFIG.d_ff)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(page: int, dtype=jnp.bfloat16):
    n_pages = SLOTS * (MAX_SEQ // page) + 1
    return page_pool_shape(n_pages, HKV, page, D), dtype


BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


def _flash_decode(q_span: int):
    from repro.kernels.flash_decode import flash_decode
    page = KV.choose_page_size(CONFIG, MAX_SEQ)
    b = SLOTS if q_span == 1 else 1
    fn = lambda q, k, v, bt, ln: flash_decode(q, k, v, bt, ln,
                                              q_span=q_span)
    return fn, [((b, HKV, G * q_span, D), BF16), _pool(page), _pool(page),
                ((b, MAX_SEQ // page), I32), ((b,), I32)]


def case_flash_decode():
    return _flash_decode(1)


def case_flash_decode_prefill_chunk():
    page = KV.choose_page_size(CONFIG, MAX_SEQ)
    return _flash_decode(KV.choose_prefill_chunk(CONFIG, MAX_SEQ, page))


def case_flash_decode_fp8():
    from repro.kernels.flash_decode import flash_decode_fp8
    cfg = dataclasses.replace(CONFIG, kv_cache_dtype=jnp.float8_e4m3fn)
    page = KV.choose_page_size(cfg, MAX_SEQ)
    fp8 = jnp.float8_e4m3fn
    return flash_decode_fp8, [((SLOTS, HKV, G, D), BF16), _pool(page, fp8),
                              _pool(page, fp8), ((HKV,), F32), ((HKV,), F32),
                              ((SLOTS, MAX_SEQ // page), I32),
                              ((SLOTS,), I32)]


def case_flash_decode_oproj():
    from repro.kernels.flash_decode import flash_decode_oproj
    page = KV.choose_page_size(CONFIG, MAX_SEQ, fused=True)
    return flash_decode_oproj, [((SLOTS, HKV, G, D), BF16), _pool(page),
                                _pool(page),
                                ((SLOTS, MAX_SEQ // page), I32),
                                ((SLOTS,), I32), ((HKV, G * D, E), BF16)]


def _kv_write(b: int, s: int):
    from repro.kernels.kv_write import write_kv
    page = KV.choose_page_size(CONFIG, MAX_SEQ)
    pool, dt = _pool(page)
    stacked = ((POOL_LAYERS,) + pool, dt)
    fn = lambda kp, vp, k, v, bt, pos, layer: write_kv(kp, vp, k, v, bt,
                                                       pos, layer)
    return fn, [stacked, stacked, ((b, s, HKV, D), BF16),
                ((b, s, HKV, D), BF16), ((b, MAX_SEQ // page), I32),
                ((b,), I32), ((), I32)]


def case_kv_write():
    return _kv_write(DECODE_SLOTS, 1)


def case_kv_write_prefill_chunk():
    page = KV.choose_page_size(CONFIG, MAX_SEQ)
    return _kv_write(1, KV.choose_prefill_chunk(CONFIG, MAX_SEQ, page))


def case_qkv_fused():
    from repro.kernels.qkv_fused import qkv_fused
    m = 512
    bm, bk, bn = best_schedule("qkv_fused", (m, HKV * D, E, G),
                               "bfloat16").tiles
    fn = lambda x, wq, wk, wv: qkv_fused(x, wq, wk, wv, bm=bm, bk=bk, bn=bn)
    return fn, [((m, E), BF16), ((E, G * HKV * D), BF16),
                ((E, HKV * D), BF16), ((E, HKV * D), BF16)]


def _matmul_fused(m: int, n: int, k: int):
    from repro.kernels.matmul_fused import matmul_fused
    bm, bk, bn = best_schedule("matmul_fused", (m, n, k), "bfloat16").tiles
    fn = lambda x, w, r: matmul_fused(x, w, residual=r, bm=bm, bk=bk, bn=bn)
    return fn, [((m, k), BF16), ((k, n), BF16), ((m, n), BF16)]


def case_matmul_fused():
    return _matmul_fused(512, F, E)


def case_matmul_fused_one_row():
    # one sequence, one token: the FFN down projection at M = 1
    return _matmul_fused(1, E, F)


def case_matmul_w8():
    from repro.kernels.matmul_q import matmul_w8
    m = SLOTS
    bm, bk, bn = best_schedule("matmul_w8", (m, F, E), "bfloat16").tiles
    fn = lambda x, w, s: matmul_w8(x, w, s, bm=bm, bk=bk, bn=bn)
    return fn, [((m, E), BF16), ((E, F), jnp.int8), ((F,), F32)]


def case_flash_attention():
    from repro.kernels import ops
    s = 1024
    fn = lambda q, k, v: ops.attention(q, k, v, interpret=False)
    return fn, [((1, s, HKV * G, D), BF16), ((1, s, HKV, D), BF16),
                ((1, s, HKV, D), BF16)]


CASES = {name[len("case_"):]: fn for name, fn in dict(globals()).items()
         if name.startswith("case_")}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = CASES[kernel]()
    text = _compile(fn, one_chip, *shapes)
    assert 'custom_call_target="tpu_custom_call"' in text, kernel


def test_paged_attention_kernel_is_named_flash_decode(one_chip):
    """A profiler trace shows the paged-attention kernel under the name
    of its custom call: ``flash_decode``, in the decode and the
    prefill-chunk forms alike."""
    import re
    for case in (case_flash_decode, case_flash_decode_prefill_chunk):
        fn, shapes = case()
        text = _compile(fn, one_chip, *shapes)
        kernels = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                             r'custom_call_target="tpu_custom_call"', text)
        assert kernels and all(re.fullmatch(r"flash_decode(\.\d+)?", k)
                               for k in kernels), kernels


def test_flash_attention_and_grad_compile_sharded_on_v5e_2x2(topo):
    """The training path's attention on a (data 2, model 2) mesh: GSPMD
    cannot partition a Mosaic kernel, so the model's attention must run
    it per device, forward and backward."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.models.layers import mha
    from repro.models.sharding import get_axis_mapping, set_axis_mapping
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    b, s = 8, 512
    act = NamedSharding(mesh, P("data", None, "model", None))
    shapes = [jax.ShapeDtypeStruct((b, s, HKV * G, D), BF16, sharding=act),
              jax.ShapeDtypeStruct((b, s, HKV, D), BF16, sharding=act),
              jax.ShapeDtypeStruct((b, s, HKV, D), BF16, sharding=act)]

    def loss(q, k, v):
        out = mha(q, k, v, interpret=False)
        return out.astype(F32).sum()

    was = get_axis_mapping()
    set_axis_mapping({"data": ("data",), "model": "model"})
    try:
        with jax.set_mesh(mesh):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                *shapes).compile().as_text()
    finally:
        set_axis_mapping(was)
    assert text.count('custom_call_target="tpu_custom_call"') >= 2


# ops that copy a pool: a slice, a relayout, an update of one and a scatter,
# loose or fused
_POOL_COPIES = {"copy", "copy-start", "copy-done", "dynamic-slice",
                "dynamic-update-slice", "scatter", "fusion"}


@pytest.fixture(scope="module")
def step_program(one_chip):
    """The engine's paged step programs at granite widths, POOL_LAYERS
    layers, compiled for the described chip: ``steps`` decode steps over
    DECODE_SLOTS slots in one scan (the decode program runs a chunk of
    them), or one prefill chunk of ``span`` tokens for one request.
    Returns (compiled, page, n_pages); compiles each shape once."""
    from repro.models import transformer as T
    from repro.models.base import build
    cfg = dataclasses.replace(CONFIG, n_layers=POOL_LAYERS)
    page = KV.choose_page_size(cfg, MAX_SEQ)
    n_pages = _pool(page)[0][0]
    on_chip = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    params = on_chip(T.param_shapes(cfg))
    done = {}

    def compile_(span: int, steps: int = 1):
        if (span, steps) in done:
            return done[span, steps]
        b = DECODE_SLOTS if span == 1 else 1
        cache = on_chip(build(KV.paged_cache_defs(cfg, b, n_pages, page),
                              "shape"))
        tok_shape = (b,) if span == 1 else (b, span)
        scalars = on_chip([jax.ShapeDtypeStruct(tok_shape, I32),
                           jax.ShapeDtypeStruct((b, MAX_SEQ // page), I32),
                           jax.ShapeDtypeStruct((b,), I32)])

        def step(params, cache, tok, bt, lengths):
            if span > 1:
                attn = KV.make_paged_span_step(cfg, bt, MAX_SEQ,
                                               use_kernel=True,
                                               interpret=False)
                return T.decode_step(cfg, params, tok, cache, lengths,
                                     attn_step=attn)
            attn = KV.make_paged_attn_step(cfg, bt, use_kernel=True,
                                           interpret=False)

            def body(carry, _):
                tok, cache, lengths = carry
                logits, cache = T.decode_step(cfg, params, tok, cache,
                                              lengths, attn_step=attn)
                return (jnp.argmax(logits, -1).astype(I32), cache,
                        lengths + 1), None
            (tok, cache, _), _ = jax.lax.scan(body, (tok, cache, lengths),
                                              None, length=steps)
            return tok, cache

        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, *scalars).compile()
        done[span, steps] = compiled, page, n_pages
        return done[span, steps]

    return compile_


@pytest.mark.parametrize("span,steps", [(1, 2), (512, 1)],
                         ids=["decode", "prefill_chunk"])
def test_step_programs_hold_no_pool_copies(step_program, span, steps):
    """Inside the decode and prefill-chunk programs the stacked page
    pools are written by the aliased writer and read by layer index: no
    op slices, relays, updates or scatters a pool-sized array — not per
    layer, and not the whole stack at the program's entry or exit."""
    import re
    compiled, page, n_pages = step_program(span, steps)
    text = compiled.as_text()
    layer_pool = f"[{n_pages},{HKV},{page},{D}]"
    stacked = f"[{POOL_LAYERS},{n_pages},{HKV},{page},{D}]"
    offending = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?)\s([a-z][\w-]*)\(", line)
        if m and m.group(2) in _POOL_COPIES and (
                layer_pool in m.group(1) or stacked in m.group(1)):
            offending.append(line.strip()[:160])
    assert not offending, "\n".join(offending)
    assert text.count('custom_call_target="tpu_custom_call"') >= 2


@pytest.mark.parametrize("span", [1, 512], ids=["decode", "prefill_chunk"])
def test_step_program_temporaries_under_one_layer_pool(step_program, span):
    """A step's temporaries stay under one layer's pool (the slice,
    relayout and write-back of pools took 239 MB at one decode step).
    One decode step: a scan of several also holds a relayout of the
    q/k/v projection weights that XLA hoists out of it, which is no
    pool's."""
    compiled, page, n_pages = step_program(span)
    pool_bytes = n_pages * HKV * page * D * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (temp, pool_bytes)
