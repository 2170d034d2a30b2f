"""Sharding rules + collectives + multi-device behaviour.

Mesh-dependent tests run in a SUBPROCESS with 8 fake host devices, so the
main pytest process keeps its single CPU device (per the dry-run contract:
only dryrun.py pins a device count).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, n_dev: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_rules_on_mesh():
    out = run_sub("""
        import jax, json
        from repro.parallel.sharding import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding as sh
        mesh = make_mesh((4, 2), ("data", "model"))
        with sh.use_mesh(mesh):
            # fused attention proj: clean 2D shard
            assert sh.spec_for("blocks/s0/attn/wq/w", (3, 64, 128)) == P(None, "data", "model")
            # indivisible dim -> replicated, not crash
            assert sh.spec_for("blocks/s0/attn/wq/w", (3, 63, 128)) == P(None, None, "model")
            # moe experts: EP over model
            assert sh.spec_for("moe/w_up/w", (8, 64, 32)) == P("model", "data", None)
            # embeddings
            assert sh.spec_for("embed/tok/w", (1024, 64)) == P("model", "data")
            # norms replicated
            assert sh.spec_for("blocks/s0/ln1/scale", (3, 64)) == P(None, None)
            # serving mode: no FSDP dim
            assert sh.spec_for("mlp/w_up/w", (64, 128), serving=True) == P(None, "model")
            assert sh.seq_axis(16) == "model"
            assert sh.seq_axis(1) is None
            assert sh.seq_axis(17) is None
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # spins a full train step in a subprocess: full lane
def test_train_step_runs_sharded():
    """One real sharded train step on an 8-device mesh: loss finite, params
    update, shardings preserved."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.sharding import make_mesh
        from repro.configs import get_config
        from repro.models import build
        from repro.parallel import sharding as sh
        from repro.train import Schedule, init_state, make_optimizer, make_train_step
        from repro.train.train_state import state_shardings

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("granite_moe_1b_a400m", smoke=True)
        api = build(cfg)
        opt = make_optimizer(cfg.optimizer, Schedule(peak_lr=1e-3))
        with sh.use_mesh(mesh):
            state = init_state(api, opt, jax.random.key(0))
            st_sh = state_shardings(state, mesh)
            state = jax.device_put(state, st_sh)
            step = make_train_step(api, opt, moe_groups=4)
            B, T = 8, 16
            rng = np.random.default_rng(0)
            batch = {
                "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            }
            batch = jax.device_put(batch, jax.tree.map(
                lambda x: sh.batch_sharding(mesh, x.ndim), batch))
            jitted = jax.jit(step, in_shardings=(st_sh, None), out_shardings=(st_sh, None))
            state2, metrics = jitted(state, batch)
            assert jnp.isfinite(metrics["loss"]), metrics
            assert int(state2.step) == 1
        print("LOSS", float(metrics["loss"]))
    """)
    assert "LOSS" in out


def test_hierarchical_psum():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import make_mesh
        from repro.parallel.collectives import hierarchical_psum
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.arange(8.0)
        y = hierarchical_psum(x, mesh, pod_axis="pod", inner_axis="data")
        # psum over pod x data (4 replicas) of the per-shard values:
        # with P((pod,data)) in-spec, x splits into 4 shards of 2 elements
        import numpy as np
        print("RESULT", np.asarray(y).tolist())
    """)
    assert "RESULT" in out


def test_compression_roundtrip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.parallel.collectives import error_feedback_compress, quantize_int8, dequantize_int8

    g = jnp.asarray(np.random.default_rng(0).normal(size=(128,)).astype(np.float32))
    bits = jax.random.bits(jax.random.key(0), g.shape, jnp.uint32)
    q, scale = quantize_int8(g, bits)
    back = dequantize_int8(q, scale)
    assert float(jnp.max(jnp.abs(back - g))) <= float(scale) + 1e-6

    grads = {"w": g}
    resid = {"w": jnp.zeros_like(g)}
    out, new_resid = error_feedback_compress(grads, resid)
    # error feedback: residual exactly the quantization error
    np.testing.assert_allclose(np.asarray(out["w"] + new_resid["w"]),
                               np.asarray(g), rtol=1e-6, atol=1e-6)


def test_hlo_analysis_on_synthetic():
    from repro.launch import hlo_analysis as H

    hlo = """\
HloModule test, entry_computation_layout={()->f32[]}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %a = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[8,8]{1,0} all-gather(%d), dimensions={0}
  %i = s32[] constant(0)
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ag)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %iv = s32[] get-tuple-element(%p2), index=0
  %k = s32[] constant(10)
  ROOT %lt = pred[] compare(%iv, %k), direction=LT
}

ENTRY %main () -> f32[] {
  %init = (s32[], f32[8,8]) parameter(0)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %r = f32[] constant(0)
}
"""
    t = H.totals(hlo)
    # dot: 2*8*8*8 = 1024 flops x 10 trips
    assert t["dot_flops_per_device"] == 1024 * 10, t
    assert t["collectives"]["all-gather"]["count"] == 10
    assert t["collectives"]["all-gather"]["bytes"] == 8 * 8 * 4 * 10
