"""``chip_smoke.py`` without a chip: its serving and logit phases on the
CPU at the reduced granite-3-8b, with the Pallas kernels in interpret
mode (the control flow a chip run takes), its comparison of the kernel
engines with the reference engine, its kernel census, and its refusal
to run when JAX finds no TPU."""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_reduced
from repro.models import transformer as T

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("granite-3-8b")          # bf16, like the chip run
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def served(smoke, model):
    """Five requests through each of the smoke's engines.  At these
    widths the blocking model pages the whole max_seq; small pages and
    chunks make the longer prompts multi-page, multi-chunk."""
    cfg, params = model
    prompts = smoke.make_prompts(cfg, 5, (16, 100), seed=0)
    runs = {name: smoke.serve_phase(
        cfg, params, prompts, 5, max_seq=128, slots=3, page_size=16,
        prefill_chunk=32, interpret=True,
        **{"use_kernel": True, **switches})
        for name, switches in smoke.ENGINES.items()}
    return prompts, runs


@pytest.mark.parametrize("fuse", [False, True])
def test_serve_phase_reduced_interpret(smoke, served, fuse):
    prompts, runs = served
    res = runs["fused" if fuse else "unfused"]
    assert dict(res["statuses"]) == {"ok": 5}
    assert res["tokens"] == 5 * 5
    assert all(len(o) == 5 for o in res["outputs"])
    # multi-chunk prompts exist, so chunked prefill ran
    assert max(len(p) for p in prompts) > res["prefill_chunk"]
    # interpret mode lowers the kernels to plain HLO: no custom calls
    assert not any(res["kernels"].values())


@pytest.mark.parametrize("fuse", [False, True])
def test_kernel_engine_matches_reference_reduced_interpret(smoke, served,
                                                           fuse):
    _, runs = served
    ref = runs["reference"]
    assert dict(ref["statuses"]) == {"ok": 5}
    res = runs["fused" if fuse else "unfused"]
    assert smoke.agreement(res["outputs"], ref["outputs"]) >= \
        smoke.AGREE_FLOOR
    assert smoke.pool_gaps(res["pools"], ref["pools"])[0] <= smoke.POOL_RTOL


def test_pool_gap_sees_a_page_from_another_slot(smoke, served):
    """One layer-1 page holding another page's K/V is a gap well past the
    tolerance."""
    _, runs = served
    ref = runs["reference"]["pools"]
    bad = [p.copy() for p in ref]
    bad[0][1, 1] = ref[0][1, 2]
    assert smoke.pool_gaps(ref, ref)[0] == 0.0
    assert smoke.pool_gaps(bad, ref)[0] > smoke.POOL_RTOL


def test_logit_phase_reduced_interpret(smoke, model):
    cfg, params = model
    prompts = smoke.make_prompts(cfg, 2, (20, 70), seed=1)
    res = smoke.logit_phase(cfg, params, prompts, page_size=16, chunk=32,
                            max_seq=128, interpret=True)
    for name, diff in res["max_abs_diff"].items():
        assert diff <= res["tol"], (name, diff, res)


def test_count_kernels_names_each_custom_call(smoke):
    line = ('  %x = bf16[8] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/while/body/'
            'NAME/pallas_call" stack_frame_id=3}')
    hlo = "\n".join([line.replace("NAME", "jit(flash_decode)"),
                     line.replace("NAME", "jit(matmul_fused)"),
                     line.replace("NAME", "jit(matmul_fused)"),
                     line.replace("NAME", "vmap(jit(_flash_forward))"),
                     '  %y = f32[8] add(%a, %b), metadata={op_name='
                     '"jit(flash_decode)/add"}'])
    assert smoke.count_kernels(hlo) == {"flash_decode": 1,
                                        "matmul_fused": 2,
                                        "_flash_forward": 1}


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stdout
    assert '"ok"' not in out.stdout
