"""The training driver on four virtual CPU devices, a (data 2, model 2)
mesh at a tiny width: a cell added from data files alone runs and is
correct, and a run with the step broken underneath is not.  Each run is
a process of its own, since the device count is fixed when JAX
starts."""

import json
import os
import subprocess
import sys

import pytest

import testkit

SEED = 2**31 + 41

# Each fault replaces the program's ``make_train_step`` before the run.
FAULTS = {
    "state_unchanged": """
def broken(cfg, tc):
    f = real(cfg, tc)
    def step(params, opt, batch):
        _, _, m = f(params, opt, batch)
        return params, opt, m
    return step
""",
    "half_batch": """
def broken(cfg, tc):
    f = real(cfg, tc)
    def step(params, opt, batch):
        return f(params, opt,
                 jax.tree.map(lambda x: x[:x.shape[0] // 2], batch))
    return step
""",
    # no gradient exchange across the data axis: each data shard's
    # slice of a parameter moves by the gradient of its own rows, and
    # the loss reported is the first shard's
    "no_exchange": """
def broken(cfg, tc):
    from jax.sharding import PartitionSpec as P
    from repro.models import transformer as T
    from repro.models.sharding import translate_tree
    from repro.optim import adamw
    loss = L.make_loss(cfg, tc)
    specs = jax.tree.leaves(translate_tree(T.param_specs(cfg, 2)),
                            is_leaf=lambda x: isinstance(x, P))

    def mix(a, b, spec):
        for ax, s in enumerate(spec):
            if s == "data" or (isinstance(s, tuple) and "data" in s):
                n = a.shape[ax] // 2
                return jnp.concatenate(
                    [jax.lax.slice_in_dim(a, 0, n, axis=ax),
                     jax.lax.slice_in_dim(b, n, a.shape[ax], axis=ax)], ax)
        return a

    def step(params, opt, batch):
        out = []
        for i in range(2):
            half = jax.tree.map(
                lambda x: x[i * x.shape[0] // 2:(i + 1) * x.shape[0] // 2],
                batch)
            out.append(jax.value_and_grad(loss, has_aux=True)(params, half))
        (l0, _), g0 = out[0]
        g1 = out[1][1]
        tree = jax.tree.structure(g0)
        g = jax.tree.unflatten(tree, [mix(a, b, s) for a, b, s in zip(
            jax.tree.leaves(g0), jax.tree.leaves(g1), specs)])
        params, opt, m = adamw.apply_updates(tc.opt, params, g, opt)
        return params, opt, {"loss": l0, **m}
    return step
""",
}


def run_tiny(root: str, seed: int, fault: str | None = None) -> dict:
    code = "\n".join([
        "import json, sys, time",
        f"sys.path[:0] = [{testkit.HERE!r}, "
        f"{os.path.join(testkit.ROOT, 'src')!r}]",
        "import jax, jax.numpy as jnp",
        "import repro.train.loop as L",
        "real = L.make_train_step",
        FAULTS.get(fault, ""),
        "L.make_train_step = broken" if fault else "",
        "import run",
        f"line = run.run_cell({root!r}, 'tiny-train', {seed}, 2.0, False, "
        "time.perf_counter())",
        "print(json.dumps(line))"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(str(tmp_path_factory.mktemp("checkout")))


def test_train_cell_from_data_files(root):
    line = run_tiny(root, SEED)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 4
    assert line["info"]["compiles_in_window"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(root, fault):
    line = run_tiny(root, SEED + 1, fault)
    assert not line["correct"]
    failed = [k for k, c in line["checks"].items()
              if c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_fp8_control_reads_above_the_limits():
    """The reference computed in float8, put in the program's place,
    fails the limits that sound runs keep."""
    code = "\n".join([
        "import json, sys",
        f"sys.path[:0] = [{testkit.HERE!r}, "
        f"{os.path.join(testkit.ROOT, 'src')!r}]",
        "import testkit, traffic, train",
        "from references import dense_train",
        "c, t = testkit.TINY_TRAIN, testkit.TINY_STEPS",
        f"tr = train.Trainer(c, t, {SEED}, lambda m: None)",
        "first = tr.first_steps(3)",
        "tr.release()",
        f"b = [traffic.batches(t, {SEED}, k, 1024) for k in range(3)]",
        "ref = dense_train.steps(c, first['p0'], b)",
        "ctl = dense_train.steps(c, first['p0'], b, mode='fp8')",
        "print(json.dumps([train.compare(first, ref), "
        "train.compare(ctl, ref)]))"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, control = json.loads(p.stdout.strip().splitlines()[-1])
    limits = testkit.TINY_TRAIN["limits"]
    assert all(sound[k] <= v for k, v in limits.items())
    assert any(control[k] > v for k, v in limits.items())
