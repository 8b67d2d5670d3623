"""The yardstick's own pieces: traffic, work counts, peaks."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import configmap
import peaks
import traffic
import work

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = os.path.join(HERE, "traffic", "chat_steady.json")
SERVE = os.path.join(HERE, "configs", "granite-3-8b-serve.json")


def chat(seed, horizon=60.0):
    return traffic.schedule(CHAT, seed, horizon, 49155, 4096, rate=4.0)


def test_same_seed_same_schedule():
    a, b = chat(2**31 + 3), chat(2**31 + 3)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_other_seed_other_schedule():
    a, b = chat(1), chat(2)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_offers_the_same_work():
    a, b = chat(1), chat(2)
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_lengths_within_clips(seed):
    t = traffic.load(CHAT)
    reqs = chat(seed, horizon=300.0)
    assert len(reqs) > 500
    p, o = t["prompt_tokens"], t["output_tokens"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert 1 <= r.max_new <= o["max"]
        assert len(r.prompt) + r.max_new <= 4096
        assert r.prompt.min() >= 0 and r.prompt.max() < 49155
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[0] >= -t["lead_in_s"]


def test_output_cut_to_max_seq():
    reqs = traffic.schedule(CHAT, 5, 300.0, 100, 1024, rate=4.0)
    assert all(len(r.prompt) + r.max_new <= 1024 for r in reqs)


def test_on_off_arrivals_keep_the_mean_and_fall_silent():
    t = {"arrivals": {"process": "on_off", "rate_per_s": 2.0, "on_s": 3.0,
                      "off_s": 7.0}, "lead_in_s": 0.0, "shape_seed": 9,
         "prompt_tokens": {"median": 50, "sigma": 0.5, "min": 8, "max": 99},
         "output_tokens": {"median": 9, "sigma": 0.5, "min": 2, "max": 20}}
    reqs = traffic.requests(t, 1, 2000.0, 100, 512)
    assert len(reqs) == pytest.approx(4000, rel=0.05)
    assert all(r.due % 10.0 < 3.0 for r in reqs)


def test_backlog_is_due_at_the_start():
    t = {"arrivals": {"process": "backlog", "requests": 7}, "lead_in_s": 5.0,
         "shape_seed": 9,
         "prompt_tokens": {"median": 50, "sigma": 0.5, "min": 8, "max": 99},
         "output_tokens": {"median": 9, "sigma": 0.5, "min": 2, "max": 20}}
    assert [r.due for r in traffic.requests(t, 1, 60.0, 100, 512)] == [-5.0] * 7


def test_shared_prefixes():
    t = {"arrivals": {"process": "poisson", "rate_per_s": 5.0},
         "lead_in_s": 0.0, "shape_seed": 9,
         "shared_prefix": {"tokens": 32, "prefixes": 3},
         "prompt_tokens": {"median": 80, "sigma": 0.3, "min": 40, "max": 99},
         "output_tokens": {"median": 9, "sigma": 0.5, "min": 2, "max": 20}}
    heads = {tuple(r.prompt[:32]) for r in traffic.requests(t, 1, 60.0,
                                                            1000, 512)}
    assert len(heads) == 3


def granite_shapes():
    from repro.configs.granite_3_8b import CONFIG
    from repro.models import transformer as T
    with open(SERVE) as f:
        c = json.load(f)
    cfg = dataclasses.replace(CONFIG, n_layers=c["num_hidden_layers"])
    return c, cfg, T.param_shapes(cfg)


def test_matmul_flops_are_twice_the_matmul_parameters():
    c, cfg, shapes = granite_shapes()
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [getattr(k, "key", "") for k in path]
        if "scale" in names:
            continue                       # norms multiply nothing
        if "embedding" in names:
            n += cfg.vocab * cfg.d_model   # tied LM head, real rows only
        else:
            n += int(np.prod(leaf.shape))
    s = configmap.shape(c)
    assert s.matmul_params == n
    assert work.matmul_flops(s, 37) == 2 * n * 37


def test_decode_and_prefill_work():
    c, _, _ = granite_shapes()
    s = configmap.shape(c)
    d = work.decode_step(s, [100, 300])
    kv = 400 * 2 * 8 * 128 * 2 * 20
    assert d["attn_bytes"] == kv + 2 * 2 * 32 * 128 * 2 * 20
    assert d["attn_flops"] == 4 * 32 * 128 * 20 * 400
    assert d["bytes"] == s.weight_bytes + d["attn_bytes"]
    p = work.prefill_chunk(s, 512, 4)
    assert p["attn_flops"] == 4 * 32 * 128 * 20 * (513 + 514 + 515 + 516)
    # a chunk does the work of its tokens decoded one by one
    one = sum(work.decode_step(s, [513 + i])["attn_flops"] for i in range(4))
    assert p["attn_flops"] == one


def test_train_step_is_three_forwards():
    c, _, _ = granite_shapes()
    s = configmap.shape(c)
    fwd = work.matmul_flops(s, 2 * 8) + 2 * work.attn_flops(s, range(1, 9))
    assert work.train_step(s, 2, 8) == 3 * fwd


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
