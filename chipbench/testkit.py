"""A tiny dense configuration and chat mix for the CPU tests, and a copy
of the benchmark's data files with a cell added that uses them."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TINY = {
    "source": "test configuration", "model_type": "granite",
    "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "vocab_size": 16000, "rope_theta": 10000.0,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12.0,
    "attention_multiplier": 1 / 64, "residual_multiplier": 0.22,
    "logits_scaling": 4.0, "initializer_range": 0.05,
    "init": {"embedding_std": 0.0018},
    "serve": {"max_seq": 512, "max_batch": 4, "pool_tokens": 2048},
    "reference": "dense_decoder",
    # sound runs read 3.4e-5-2.7e-4 on the CPU, the int8 control
    # 6.9e-4-1.3e-3 (seeds 11-16)
    "limits": {"logit_gap": 4.5e-4},
}

TINY_CHAT = {
    "driver": "serve",
    "arrivals": {"process": "poisson", "rate_per_s": 4.0}, "lead_in_s": 1.0,
    "prompt_tokens": {"median": 60, "sigma": 0.8, "min": 8, "max": 300},
    "output_tokens": {"median": 24, "sigma": 0.8, "min": 2, "max": 60},
    "shape_seed": 5,
}


TINY_TRAIN = dict(
    {k: v for k, v in TINY.items() if k not in ("serve", "limits")},
    num_hidden_layers=2, torch_dtype="float32", vocab_size=1024,
    train={"mesh": {"data": 2, "model": 2},
           "opt": {"lr": 3e-4, "warmup_steps": 0, "b1": 0.9, "b2": 0.95,
                   "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0,
                   "total_steps": 10000, "min_lr_frac": 0.1}},
    reference="dense_train",
    # float32 parameters, a vocabulary the shards divide: sound runs read
    # loss 1.4e-7, gradient 2.1e-7, change 2.4e-6 at most (seeds
    # 101-103, 2**31+11..14); half a batch or no exchange read at least
    # 7e-5, 0.026 and 0.017, a state left unchanged 1, the reference in
    # float8 1.3e-5, 0.0067 and 0.0050 at least (seeds 101-103)
    limits={"loss_gap": 1e-6, "grad_gap": 1e-5, "update_gap": 1e-4})

TINY_STEPS = {"driver": "train", "batch": 4, "seq_len": 64}

# a backlog of long prompts, all due before the window opens
TINY_BACKLOG = dict(
    TINY_CHAT, arrivals={"process": "backlog", "requests": 96},
    prompt_tokens={"median": 200, "sigma": 0.4, "min": 100, "max": 400},
    output_tokens={"median": 8, "sigma": 0.6, "min": 4, "max": 24})

# bursts of sessions that share system prompts, served with the prefix
# cache on: only data, as a later cell would bring it
TINY_PREFIX = dict(TINY, serve=dict(TINY["serve"], prefix_cache=True))
TINY_SESSIONS = dict(
    TINY_CHAT, arrivals={"process": "on_off", "rate_per_s": 4.0,
                         "on_s": 0.5, "off_s": 0.5},
    shared_prefix={"tokens": 48, "prefixes": 2})


def make_root(dst: str) -> str:
    """``dst`` becomes a checkout's data: BENCHMARK.json and the
    benchmark's data files, plus config files, traffic files and the
    entries of cells ``tiny-chat``, ``tiny-sessions`` and
    ``tiny-backlog`` (one chip) and ``tiny-train`` (a 2 x 2 mesh) that
    use them.  No code is added or changed."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    path = bench["paths"][0]
    shutil.copytree(os.path.join(ROOT, path), os.path.join(dst, path),
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "testdata"))
    cfg_file = f"{path}/configs/tiny-dense.json"
    with open(os.path.join(dst, cfg_file), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(dst, path, "traffic", "tiny_chat.json"),
              "w") as f:
        json.dump(TINY_CHAT, f)
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": cfg_file, "reduced": [],
                             "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-chat", "config": "tiny-dense",
                               "traffic": "tiny_chat", "chips": 1,
                               "why": "CPU test"})
    prefix_file = f"{path}/configs/tiny-dense-prefix.json"
    with open(os.path.join(dst, prefix_file), "w") as f:
        json.dump(TINY_PREFIX, f)
    with open(os.path.join(dst, path, "traffic", "tiny_sessions.json"),
              "w") as f:
        json.dump(TINY_SESSIONS, f)
    bench["configs"].append({"name": "tiny-dense-prefix", "source": "test",
                             "file": prefix_file, "reduced": [],
                             "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-sessions",
                               "config": "tiny-dense-prefix",
                               "traffic": "tiny_sessions", "chips": 1,
                               "why": "CPU test"})
    with open(os.path.join(dst, path, "traffic", "tiny_backlog.json"),
              "w") as f:
        json.dump(TINY_BACKLOG, f)
    bench["workloads"].append({"name": "tiny-backlog", "config": "tiny-dense",
                               "traffic": "tiny_backlog", "chips": 1,
                               "why": "CPU test"})
    train_file = f"{path}/configs/tiny-dense-train.json"
    with open(os.path.join(dst, train_file), "w") as f:
        json.dump(TINY_TRAIN, f)
    with open(os.path.join(dst, path, "traffic", "tiny_steps.json"),
              "w") as f:
        json.dump(TINY_STEPS, f)
    bench["configs"].append({"name": "tiny-dense-train", "source": "test",
                             "file": train_file, "reduced": [],
                             "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-train",
                               "config": "tiny-dense-train",
                               "traffic": "tiny_steps", "chips": 4,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads", [])
        if any(w.endswith("chat-steady") for w in ws):
            m["workloads"] += ["tiny-chat", "tiny-sessions", "tiny-backlog"]
    if not any(m["name"] == "train_tok_s" for m in bench["end_to_end"]):
        bench["end_to_end"].append(
            {"name": "train_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-train"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
