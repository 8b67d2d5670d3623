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
SLOTS = 8
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
