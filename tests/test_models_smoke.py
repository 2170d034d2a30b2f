"""Per-arch smoke tests: reduced same-family configs, one forward/train step
on CPU, asserting output shapes + finiteness (spec deliverable f)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import build

# full-lane suite: excluded from the CI fast lane (pytest -m "not slow")
pytestmark = pytest.mark.slow

B, T = 2, 16


def _batch_for(api, kind="train"):
    cfg = api.cfg
    rng = np.random.Generator(np.random.Philox(key=np.uint64(7)))
    toks = rng.integers(0, cfg.vocab_size, size=(B, T), dtype=np.int64).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    if kind == "train":
        batch["labels"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(B, T), dtype=np.int64).astype(np.int32))
    if cfg.vision_prefix:
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.vision_prefix, cfg.d_model)), jnp.bfloat16)
    if cfg.encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_positions, cfg.d_model)), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_loss_finite(arch):
    cfg = get_config(arch, smoke=True)
    api = build(cfg)
    params = api.init(jax.random.key(0))
    loss, metrics = jax.jit(lambda p, b: api.loss(p, b))(params, _batch_for(api))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss={loss}"
    assert bool(jnp.isfinite(metrics["ce"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_grad_step_finite(arch):
    cfg = get_config(arch, smoke=True)
    api = build(cfg)
    params = api.init(jax.random.key(1))
    batch = _batch_for(api)

    def loss_fn(p):
        return api.loss(p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves
    for g in leaves:
        assert bool(jnp.all(jnp.isfinite(g))), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch):
    cfg = get_config(arch, smoke=True)
    api = build(cfg)
    params = api.init(jax.random.key(2))
    batch = _batch_for(api, kind="prefill")
    S = T + 4
    logits, caches = jax.jit(
        lambda p, b: api.prefill(p, b, cache_len=S))(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    logits2, caches = jax.jit(api.decode_step)(params, caches, tok,
                                               jnp.asarray(T, jnp.int32))
    assert logits2.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2)))


def _f32(cfg):
    # float32 for tight tolerances; capacity_factor high enough that MoE
    # token dropping cannot differ between the full forward (T tokens) and
    # prefill (T-1 tokens) -- drops are the one legitimate divergence.
    return dataclasses.replace(cfg, dtype="float32", capacity_factor=8.0)


@pytest.mark.parametrize("arch", ["yi_34b", "rwkv6_1_6b", "jamba_v0_1_52b", "gemma3_27b"])
def test_decode_matches_forward(arch):
    """Prefill(T-1) + decode(last) must reproduce the full-forward logits of
    the last position (cache correctness, incl. ring/SSM/hybrid caches)."""
    cfg = _f32(get_config(arch, smoke=True))
    api = build(cfg)
    params = api.init(jax.random.key(3))
    rng = np.random.Generator(np.random.Philox(key=np.uint64(11)))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), jnp.int32)

    from repro.models import transformer

    hidden, _, _ = transformer.forward(params, cfg, toks, mode="train")
    W = transformer.unembed_matrix(params, cfg, hidden.dtype)
    full_logits = (hidden[:, -1] @ W).astype(jnp.float32)

    logits_p, caches = api.prefill(params, {"tokens": toks[:, : T - 1]}, cache_len=T)
    logits_d, _ = api.decode_step(params, caches, toks[:, T - 1 :],
                                  jnp.asarray(T - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_gemma3_ring_cache_window():
    """Sliding-window decode must equal full-context attention restricted to
    the window even when the ring buffer has wrapped several times."""
    cfg = _f32(get_config("gemma3_27b", smoke=True))
    api = build(cfg)
    params = api.init(jax.random.key(4))
    rng = np.random.Generator(np.random.Philox(key=np.uint64(13)))
    T_long = 24  # > sliding_window=8 -> ring wraps
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, T_long)), jnp.int32)

    from repro.models import transformer

    hidden, _, _ = transformer.forward(params, cfg, toks, mode="train")
    W = transformer.unembed_matrix(params, cfg, hidden.dtype)
    want = (hidden[:, -1] @ W).astype(jnp.float32)

    logits, caches = api.prefill(params, {"tokens": toks[:, :8]}, cache_len=T_long)
    for t in range(8, T_long):
        logits, caches = api.decode_step(params, caches, toks[:, t : t + 1],
                                         jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_param_counts_sane():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        n = cfg.param_count()
        assert n > 0
        a = cfg.active_param_count()
        assert 0 < a <= n
    # spot-check the headline sizes (within 20% of the advertised params)
    assert abs(get_config("yi_34b").param_count() / 34e9 - 1) < 0.2
    assert abs(get_config("mistral_nemo_12b").param_count() / 12e9 - 1) < 0.25
    assert abs(get_config("whisper_large_v3").param_count() / 1.55e9 - 1) < 0.3
    mav = get_config("llama4_maverick_400b_a17b")
    assert abs(mav.param_count() / 400e9 - 1) < 0.25
    assert abs(mav.active_param_count() / 17e9 - 1) < 0.35
