"""The trace reduction and the per-layer readers, on a trace recorded on
one TPU v5e: one engine step of granite-3-8b (20 layers, 32 slots), an
8-step decode program and a 512-token prefill chunk."""

import os

import pytest

import layerkit
import serve
import trace_reduce
import work

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "decode_step.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(TRACE)


def test_one_device_one_step(red):
    assert red["devices"] == 1 and len(red["steps"]) == 1
    mods = red["steps"][0]["modules"]
    assert len(mods["jit__decode_fn"]) == 1 and len(mods["jit_chunk"]) == 1
    assert mods["jit__decode_fn"][0] == pytest.approx(0.407521356)


def test_busy_and_idle_add_up(red):
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], abs=1e-6)
    assert red["collective_s"] == 0


def test_kernel_calls_by_program(red):
    ops = red["steps"][0]["ops"]
    # 8 decode steps and one chunk, one paged-attention call per layer
    assert ops[("jit__decode_fn", "flash_decode")][1] == 8 * 20
    assert ops[("jit_chunk", "flash_decode")][1] == 20
    # loops hold other ops and are no class of their own
    assert not any(name == "while" for _, name in ops)
    assert "while" not in red["ops"]


def test_op_class():
    assert trace_reduce.op_class(
        "%fusion.12 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop") \
        == "fusion"
    assert trace_reduce.op_class("%flash_decode.9 = (bf16[1]) custom-call()") \
        == "flash_decode"
    assert trace_reduce.module_name("jit_chunk(1473440302)") == "jit_chunk"


def test_union_and_clip():
    u = trace_reduce.union([(0, 2), (1, 3), (5, 6)])
    assert u == [[0, 3], [5, 6]]
    assert trace_reduce.clip(u, 1, 5.5) == pytest.approx(2.5)


reader = layerkit.reader


@pytest.fixture(scope="module")
def ctx(red):
    shape = work.Shape(n_layers=20, d_model=4096, n_heads=32, n_kv_heads=8,
                       head_dim=128, d_ff=12800, vocab=49155)
    # what the harness would have seen: 30 slots decoding 8 tokens each
    # at 1000 keys, and one 512-token chunk after 512 cached tokens
    step = serve.Step(0.1, 0.2, [(1000, 8)] * 30, [(512, 512, 1500)])
    return {"trace": red, "trace_span": [0.0, 1.0], "steps": [step],
            "shape": shape, "device_kind": "TPU v5 lite",
            "prefill_chunk": 512, "recs": {}, "window_s": 1.0, "t_end": 1.0}


def test_decode_step_ms(ctx):
    assert reader("decode_step_ms.chat")(ctx) == pytest.approx(
        407.521356 / 8)


def test_prefill_chunk_ms(ctx):
    assert reader("prefill_chunk_ms.chat")(ctx) == pytest.approx(63.796303)


def test_shares_within_bounds(ctx):
    for name in ("step_mfu.chat", "attn_roofline.chat", "idle_share.chat"):
        v = reader(name)(ctx)
        assert 0 < v < 100, (name, v)


def test_roofline_counts_the_work():
    s = work.Shape(20, 4096, 32, 8, 128, 12800, 49155)
    least = 8 * work.attn_bytes(s, [1000] * 30, 30) / 819e9
    assert least == pytest.approx(8 * (30 * 1000 * 81920 + 2 * 30 * 4096
                                       * 2 * 20) / 819e9)


def test_nothing_to_read_gives_nothing():
    empty = {"trace": None, "trace_span": None, "steps": [],
             "recs": {}, "window_s": 1.0, "t_end": 1.0,
             "shape": work.Shape(20, 4096, 32, 8, 128, 12800, 49155),
             "device_kind": "TPU v5 lite", "prefill_chunk": 512}
    for name in ("decode_step_ms.chat", "prefill_chunk_ms.chat",
                 "step_mfu.chat", "attn_roofline.chat", "idle_share.chat"):
        assert reader(name)(empty) is None
    assert reader("queue_wait_p95_ms.chat")(empty) is None
