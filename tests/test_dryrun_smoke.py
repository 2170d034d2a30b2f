"""Dry-run machinery smoke test: lower+compile a smoke-scale arch on a tiny
(2,2) production-mesh analog in a subprocess (8 fake devices), exercising
the same build_cell / sharding / analysis code paths as the 512-device run."""
import os
import subprocess
import sys
import textwrap

import pytest

# full-lane suite: excluded from the CI fast lane (pytest -m "not slow")
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code, n_dev=8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-2500:])
    return out.stdout


def test_train_cell_lowers_and_compiles():
    out = _run("""
        import jax, jax.numpy as jnp, math
        from repro.parallel.sharding import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, ShapeSpec
        from repro.models import build
        from repro.parallel import sharding as sh
        from repro.train import Schedule, make_optimizer, make_train_step
        from repro.train.train_state import TrainState, state_shardings
        from repro.launch import hlo_analysis

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("yi_34b", smoke=True)
        api = build(cfg)
        opt = make_optimizer(cfg.optimizer, Schedule())
        with sh.use_mesh(mesh):
            step = make_train_step(api, opt, moe_groups=4)
            params_s = jax.eval_shape(api.init, jax.random.key(0))
            opt_s = jax.eval_shape(opt.init, params_s)
            state_s = TrainState(jax.ShapeDtypeStruct((), jnp.int32), params_s, opt_s)
            batch_s = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                       "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
            st_sh = state_shardings(state_s, mesh)
            b_sh = jax.tree.map(lambda s: sh.batch_sharding(mesh, len(s.shape)), batch_s)
            lowered = jax.jit(step, in_shardings=(st_sh, b_sh)).lower(state_s, batch_s)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        tot = hlo_analysis.totals(compiled.as_text())
        assert tot["dot_flops_per_device"] > 0
        assert mem.temp_size_in_bytes > 0
        print("OK flops", tot["dot_flops_per_device"])
    """)
    assert "OK" in out


def test_decode_cell_serving_layout():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build
        from repro.parallel import sharding as sh

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("gemma3_27b", smoke=True)
        api = build(cfg)
        with sh.use_mesh(mesh):
            params_s = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16
                                               if s.dtype == jnp.float32 else s.dtype),
                jax.eval_shape(api.init, jax.random.key(0)))
            caches_s = jax.eval_shape(lambda: api.init_caches(8, 64))
            p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                sh.param_specs(params_s, serving=True),
                                is_leaf=lambda x: isinstance(x, P))
            fn = lambda p, c, t, pos: api.decode_step(p, c, t, pos)
            lowered = jax.jit(fn, in_shardings=(p_sh, None, None, None)).lower(
                params_s, caches_s,
                jax.ShapeDtypeStruct((8, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        print("OK", compiled.memory_analysis().argument_size_in_bytes)
    """)
    assert "OK" in out


def test_elastic_mesh_shapes():
    out = _run("""
        import jax
        from repro.launch.mesh import make_host_mesh
        m = make_host_mesh()
        assert m.size == 8, m
        assert m.axis_names == ("data", "model")
        m2 = make_host_mesh(max_devices=6)
        assert m2.size == 6
        print("OK", dict(zip(m.axis_names, m.devices.shape)))
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a (4,2) mesh, restore onto (2,2) -- elastic rescale."""
    out = _run("""
        import jax, jax.numpy as jnp, tempfile
        from repro.parallel.sharding import make_mesh
        from repro.checkpoint import Checkpointer
        from repro.configs import get_config
        from repro.models import build
        from repro.parallel import sharding as sh
        from repro.train import Schedule, init_state, make_optimizer
        from repro.train.train_state import state_shardings

        cfg = get_config("mistral_nemo_12b", smoke=True)
        api = build(cfg)
        opt = make_optimizer("adamw", Schedule())
        mesh_a = make_mesh((4, 2), ("data", "model"))
        with sh.use_mesh(mesh_a):
            state = init_state(api, opt, jax.random.key(0))
            state = jax.device_put(state, state_shardings(state, mesh_a))
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(3, state)
        # elastic restart: fewer devices
        mesh_b = make_mesh((2, 2), ("data", "model"))
        with sh.use_mesh(mesh_b):
            like = init_state(api, opt, jax.random.key(1))
            restored = ck.restore(3, like, mesh=mesh_b)
        import numpy as np
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert int(restored.step) == int(state.step)
        print("OK resharded")
    """, n_dev=8)
    assert "OK" in out
