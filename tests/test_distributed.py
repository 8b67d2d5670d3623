"""Multi-device tests (subprocess: device count must be set pre-jax-init).

Covers the shard_map MoE dispatch vs the dense reference, sharded
train-step lowering on a small mesh, and the fsdp-vs-tp axis mappings.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# every case here spawns a subprocess that compiles sharded jax programs
# (minutes, not seconds): fast-lane runs skip them with -m "not slow"
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # the virtual devices are CPU devices: never let a child reach for
    # an accelerator runtime
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_shardmap_moe_matches_dense_reference():
    run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced
        from repro.models import layers as L
        from repro.models.base import build
        from repro.models.sharding import set_axis_mapping

        cfg = dataclasses.replace(get_reduced('qwen3-moe-235b-a22b'),
                                  dtype=jnp.float32, capacity_factor=8.0)
        params = build(L.moe_defs(cfg, 2), 'init', jax.random.PRNGKey(0))
        mesh = make_mesh((4, 2), ('data', 'model'))
        set_axis_mapping({'data': ('data',), 'model': 'model'})
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                              jnp.float32)
        ref_out, _ = L._moe_apply_ref(cfg, params, x)
        with jax.set_mesh(mesh):
            out, aux = jax.jit(lambda p, x: L.moe_apply(cfg, p, x))(
                params, x)
        err = float(jnp.max(jnp.abs(out - ref_out)))
        assert err < 1e-4, err
        print('OK', err)
    """)


def test_sharded_train_step_lowers_and_runs():
    """A REAL sharded train step (not just lower): 2x2 mesh, reduced arch,
    runs one step and checks finite loss + sharded params."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.models import transformer as T
        from repro.models.sharding import set_axis_mapping, translate_tree
        from repro.optim import adamw
        from repro.train.loop import TrainConfig, make_train_step
        from repro.data.pipeline import make_batch

        cfg = dataclasses.replace(
            get_reduced('granite-3-8b'), d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=128)
        mesh = make_mesh((2, 2), ('data', 'model'))
        mapping = {'data': ('data',), 'model': 'model'}
        set_axis_mapping(mapping)
        specs = translate_tree(T.param_specs(cfg, 2), mapping)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda k: T.init_params(cfg, k, 2),
                out_shardings=shardings)(jax.random.PRNGKey(0))
            opt = adamw.init_state(params)
            step = jax.jit(make_train_step(cfg, TrainConfig()))
            batch = make_batch(cfg, 32, 4, 0)
            params, opt, m = step(params, opt, batch)
        assert np.isfinite(float(m['loss']))
        print('OK', float(m['loss']))
    """)


def test_chip_smoke_train_phase_matches_one_device():
    """``chip_smoke.py --four-chips``'s phase on 4 virtual devices at a
    small width: sharded losses and updates equal one device's, params
    sharded."""
    out = run_py(f"""
        import dataclasses, importlib.util, jax
        from repro.configs import get_reduced
        spec = importlib.util.spec_from_file_location(
            'chip_smoke', {os.path.join(SRC, '..', 'chip_smoke.py')!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        cfg = dataclasses.replace(get_reduced('granite-3-8b'), n_layers=2)
        res = smoke.train_phase(cfg, jax.devices(), seq_len=32, batch=4)
        assert res['finite'], res
        assert res['max_rel_diff'] <= smoke.LOSS_RTOL, res
        assert res['update_gap'] <= smoke.PARAM_RTOL, res
        assert res['bytes_on_device0'] <= 0.3 * res['param_bytes'], res
        print('OK', res['max_rel_diff'])
    """, devices=4)
    assert out.startswith("OK")


def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery end-to-end on an 8-device (4,2) mesh with a
    reduced config (fast): lower + compile + artifact fields."""
    run_py("""
        import dataclasses, jax
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced, SHAPES, ARCHS
        from repro.launch import shapes as S
        from repro.models.sharding import set_axis_mapping
        import repro.launch.dryrun as dr

        cfg = get_reduced('gemma2-9b')
        mesh = make_mesh((4, 2), ('data', 'model'))
        shape = dataclasses.replace(SHAPES['train_4k'], seq_len=64,
                                    global_batch=8)
        mapping = S.axis_mapping(cfg, shape, mesh)
        set_axis_mapping(mapping)
        import repro.configs as C
        C.SHAPES['tiny_train'] = dataclasses.replace(
            shape, name='tiny_train')
        low = S.input_specs(cfg, 'tiny_train', mesh, model_ax=2)
        with jax.set_mesh(mesh):
            compiled = jax.jit(low.fn, in_shardings=low.in_shardings,
                               out_shardings=low.out_shardings
                               ).lower(*low.args_shapes).compile()
        coll = dr.collective_bytes(compiled.as_text())
        assert sum(coll.values()) > 0  # TP all-reduces must exist
        print('OK', coll)
    """)


def test_fsdp_mapping_removes_tp_collectives():
    """fsdp parallelism must produce strictly fewer collective bytes than
    tp_fsdp on the same tiny dense cell (the §Perf it.1 claim, in CI)."""
    out = run_py("""
        import dataclasses, jax
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced, SHAPES
        from repro.launch import shapes as S
        from repro.models.sharding import set_axis_mapping
        import repro.configs as C
        import repro.launch.dryrun as dr

        cfg = get_reduced('granite-3-8b')
        mesh = make_mesh((4, 2), ('data', 'model'))
        C.SHAPES['tiny_train'] = dataclasses.replace(
            SHAPES['train_4k'], name='tiny_train', seq_len=64,
            global_batch=8)
        totals = {}
        for par in ('tp_fsdp', 'fsdp'):
            shape = C.SHAPES['tiny_train']
            set_axis_mapping(S.axis_mapping(cfg, shape, mesh, par))
            low = S.input_specs(cfg, 'tiny_train', mesh, parallelism=par)
            with jax.set_mesh(mesh):
                comp = jax.jit(low.fn, in_shardings=low.in_shardings,
                               out_shardings=low.out_shardings
                               ).lower(*low.args_shapes).compile()
            totals[par] = sum(dr.collective_bytes(comp.as_text()).values())
        assert totals['fsdp'] < totals['tp_fsdp'], totals
        print('OK', totals)
    """)
    assert "OK" in out
