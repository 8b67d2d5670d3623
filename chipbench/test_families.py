"""Model families: the granite family's mapping, folds and draw, the
refusal of a model type with no family file, and a family that joins
the benchmark as new files only."""

import dataclasses
import hashlib
import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import configmap
import testkit
import weights
from families import granite

SERVE = os.path.join(testkit.HERE, "configs", "granite-3-8b-serve.json")
SEED = 2**31 + 17
# sha256 of the testkit-size granite draw from SEED, leaves in tree order,
# as the harness drew it before families had files of their own
TINY_DRAW_SHA256 = \
    "de6c73633caac3e77416a8ff60bb4fd6a6652a1477481570076c3fbf74dce9c2"


def serve_file() -> dict:
    with open(SERVE) as f:
        return json.load(f)


@pytest.mark.parametrize("change", [
    {"hidden_act": "gelu"}, {"tie_word_embeddings": False},
    {"attention_bias": True}, {"model_type": "mamba2"},
    {"sliding_window": 4096}, {"torch_dtype": "float16"}])
def test_config_keys_mapped_or_refused(change):
    c = serve_file()
    assert configmap.check(c) is granite
    with pytest.raises(ValueError):
        configmap.check(dict(c, **change))


def test_granite_file_maps_to_the_published_widths():
    from repro.configs.granite_3_8b import CONFIG
    cfg = configmap.model_config(serve_file())
    assert dataclasses.replace(cfg, name=CONFIG.name, n_layers=40) == CONFIG


def test_folds_are_exact():
    """The served model with the folds applied is the published one:
    scaling the residual stream by k turns the program's epsilon into
    the published one, and each scalar lands on the weights it
    multiplies."""
    c = serve_file()
    f = granite.folds(c)
    assert weights.folds(c) == f
    k = math.sqrt(granite.PROGRAM_EPS / c["rms_norm_eps"])
    assert f["embedding"] * math.sqrt(4096) == pytest.approx(
        c["embedding_multiplier"] * k)
    assert f["wq"] * 128 ** -0.5 == pytest.approx(c["attention_multiplier"])
    assert f["wo"] == f["w_down"] == pytest.approx(
        c["residual_multiplier"] * k)
    assert f["final_norm"] * f["embedding"] == pytest.approx(
        1 / c["logits_scaling"])


def test_unknown_model_type_names_the_families_present():
    assert "granite" in configmap.families()
    with pytest.raises(ValueError, match=r"granitemoehybrid.*'granite'"):
        configmap.model_config(dict(serve_file(),
                                    model_type="granitemoehybrid"))


def test_granite_draw_is_pinned():
    """The same seed draws the same granite weights, bit for bit."""
    from repro.models import transformer as T
    c = testkit.TINY
    p = weights.make_weights(T.param_shapes(configmap.model_config(c)), c,
                             SEED)
    h = hashlib.sha256()
    for x in jax.tree.leaves(p):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == TINY_DRAW_SHA256


# A family of its own, as a new model would bring it: granite's layout and
# scalars, with the norm scales drawn by a rule of the family's own.
NEW_FAMILY = '''"""A test family: granite's layout with norm scales drawn from
U(0.9, 1.1)."""

import jax
import jax.numpy as jnp

from families import granite
from families.granite import check, folds, model_config, shape  # noqa


def draw(names, c):
    if "scale" in names:
        return lambda key, shp: jax.random.uniform(
            key, shp, jnp.float32, 0.9, 1.1)
    return granite.draw(names, c)
'''

DRIVE = '''import json, os, sys, time
root, src, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
sys.path[:0] = [os.path.join(root, "chipbench"), src]
import run, configmap, weights
from repro.models import transformer as T
for seconds, trace in ((3.0, False), (5.0, True)):
    print(json.dumps(run.run_cell(root, "tiny-family-chat", seed, seconds,
                                  trace, time.perf_counter())))
with open(os.path.join(root, "chipbench", "configs", "tiny-family.json")) as f:
    c = json.load(f)
p = weights.make_weights(T.param_shapes(configmap.model_config(c)), c, seed)
s = p["layers"][0]["norm1"]["scale"]
print(json.dumps({"family": configmap.family(c).__name__,
                  "scale": [float(s.min()), float(s.max())]}))
'''


def tree_digest(top: str, skip=()) -> dict:
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x not in ("out", "__pycache__",
                                                "testdata")]
        for n in files:
            rel = os.path.relpath(os.path.join(d, n), top)
            if rel not in skip and not n.endswith(".pyc"):
                with open(os.path.join(d, n), "rb") as f:
                    out[rel] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_family_is_new_files_only(tmp_path):
    """A model type of its own joins as a family file and a configuration
    file beside the existing dense reference, with every file of the
    harness as it is; a checkout that holds them runs a CPU cell and a
    traced read, correct, in a process of its own."""
    root = testkit.make_root(str(tmp_path / "checkout"))
    bench_dir = os.path.join(root, "chipbench")
    with open(os.path.join(bench_dir, "families", "tinyfam.py"), "w") as f:
        f.write(NEW_FAMILY)
    with open(os.path.join(bench_dir, "configs", "tiny-family.json"),
              "w") as f:
        json.dump(dict(testkit.TINY, model_type="tinyfam"), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-family", "source": "test",
                             "file": "chipbench/configs/tiny-family.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-family-chat",
                               "config": "tiny-family",
                               "traffic": "tiny_chat", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-chat" in m.get("workloads", []):
            m["workloads"].append("tiny-family-chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # every file the harness had is there unchanged
    mine = tree_digest(testkit.HERE)
    theirs = tree_digest(bench_dir)
    assert all(theirs[k] == v for k, v in mine.items())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_TUNE_CACHE=str(tmp_path / "schedules.json"))
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, root,
         os.path.join(testkit.ROOT, "src"), str(SEED)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
    assert len(lines) == 3, (p.stdout[-2000:], p.stderr[-4000:])
    plain, traced, drawn = [json.loads(x) for x in lines]
    for line in (plain, traced):
        assert line["correct"], line["checks"]
        assert line["attempted"] > 0 and line["failed"] == 0
    assert set(plain["metrics"]) == {"ttft_mean_ms", "tpot_p95_ms",
                                     "serve_tok_s", "setup_s"}
    assert set(traced["metrics"]) == {"queue_wait_p95_ms.chat",
                                      "ttft_p95_ms.chat"}
    assert drawn["family"] == "families.tinyfam"
    lo, hi = drawn["scale"]
    assert 0.89 < lo < 1.0 < hi < 1.11          # U(0.9, 1.1) in bf16
