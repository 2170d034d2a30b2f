"""Compile rehearsals for the TPU: the fused kernels and one sharded step are
AOT-compiled for a described v5e:2x2 topology (no chip attached), so a kernel
that Mosaic refuses fails here instead of on the chip.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file. Compiles of the fused kernels take a second or two each.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.limbs import ModPlan
from repro.hash import Hasher, HashPlan, HashSpec, ShardedHasher
from repro.hash.distributed import probe_owner_local
from repro.kernels import gf_multihash as gfmh
from repro.kernels import multihash as mhk
from repro.kernels.autotune import CANDIDATES
from repro.parallel.sharding import make_mesh

U32 = jnp.uint32

# (B, N, K, block_b, block_n): the corpus shape, and the serve shape that
# `Hasher._hash_limbs` produces for a few prompts of up to 24 tokens
# (width W = even(24 + 1) = 26, not a multiple of 128; rows padded to 8)
SHAPES = {"corpus": (4096, 4096, 4, 8, 1024), "serve": (8, 26, 1, 8, 26)}
FAMILIES = ("multilinear", "multilinear_hm", "gf_multilinear",
            "gf_multilinear_hm")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(sharding, family, B, N, K, block_b, block_n, mod_m):
    """AOT-compile one fused-kernel launch; returns the compiled text."""
    def sds(s, dt=U32):
        return jax.ShapeDtypeStruct(s, dt, sharding=sharding)

    plan = None if mod_m is None else ModPlan.for_modulus(mod_m)
    return jax.jit(_kernel_fn(family, block_b, block_n, plan)).lower(
        sds((B, N)), sds((K, N)), sds((K, N)), sds((B,), jnp.int32),
        sds((K, 2))).compile().as_text()


def _kernel_fn(family, block_b, block_n, mod_m):
    if family.startswith("gf_"):
        def fn(t, kh, kl, lens, m1):
            return gfmh.gf_multihash_blocks(
                t, kl, lens, m1, family=family, block_b=block_b,
                block_n=block_n, mod_m=mod_m)
    else:
        def fn(t, kh, kl, lens, m1):
            return mhk.multihash_blocks(
                t, kh, kl, lens, m1, family=family, block_b=block_b,
                block_n=block_n, mod_m=mod_m)
    return fn


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("mod_m", [None, 4097, 7_188_793_783],
                         ids=["plain", "mod_m", "mod_m_wide"])
@pytest.mark.parametrize("family", FAMILIES)
def test_fused_kernel_compiles_for_v5e(one_chip, family, mod_m, shape):
    text = _compile_kernel(one_chip, family, *SHAPES[shape], mod_m)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block", CANDIDATES, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("family", ["multilinear", "gf_multilinear_hm"])
def test_autotune_candidates_compile_for_v5e(one_chip, family, block):
    """Every block shape the autotuner may pick lowers (512 x 1024, K=4)."""
    text = _compile_kernel(one_chip, family, 512, 1024, 4, *block, 4097)
    assert "tpu_custom_call" in text


def test_sharded_hasher_step_compiles_on_four_chips(topo):
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    B, N, K = 4096, 1024, 4
    h = Hasher.from_spec(HashSpec(n_hashes=K, out_bits=64,
                                  variable_length=True), max_len=N,
                         plan=HashPlan(backend="pallas"))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    h_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), h)
    step = jax.jit(lambda hs, t, n: ShardedHasher(hs, mesh)(t, n))
    compiled = step.lower(
        h_sds, jax.ShapeDtypeStruct((B, N), U32, sharding=rows),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rows)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.output_shardings
    assert out.mesh.size == 4 and out.spec[0] == "data", out
    assert np.prod(mesh.devices.shape) == 4


def test_wide_probe_split_compiles_on_four_chips(topo):
    """The dedup_docs_d4 filter's probes on a v5e host's four chips: the
    wide mod_m epilogue in the kernel, then the (owner, local) split."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    B, N, K, m = 4096, 1024, 9, 7_188_793_783
    m_local = -(-m // 4)
    h = Hasher.from_spec(HashSpec(n_hashes=K, out_bits=64,
                                  variable_length=True), max_len=N,
                         plan=HashPlan(backend="pallas"))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    h_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), h)

    def step(hs, t, n):
        r = ShardedHasher(hs, mesh).probe_indices(t, m, n)
        return probe_owner_local(r, m, m_local, 4)

    compiled = jax.jit(step).lower(
        h_sds, jax.ShapeDtypeStruct((B, N), U32, sharding=rows),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rows)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grouped_admission_compiles_on_four_chips(topo):
    """The dedup_docs_d4 filter's whole routed admission as a wave of its
    mix is staged on a v5e host's four chips: two length-sorted row groups
    cut from one token arena, one kernel each, then the (owner, local)
    exchange, the test and the scatter. The 1.80 GB shard of bits is only
    described."""
    from repro.hash.distributed import TokenArena, _admission_surfaces

    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    m, k, D = 7_188_793_783, 9, 4
    groups = ((3840, 2048), (256, 32768))
    surfaces = _admission_surfaces(mesh, "data", m, ModPlan.for_modulus(m))
    assert sorted(surfaces) == ["_add_dev", "_add_rt", "_admit_dev",
                                "_admit_rt", "_contains_dev", "_contains_rt"]
    h = Hasher.from_spec(HashSpec(n_hashes=k, out_bits=64,
                                  variable_length=True), max_len=32768,
                         plan=HashPlan(backend="pallas"))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    h_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), h)
    R, S = sum(b for b, _ in groups), sum(b * w for b, w in groups)
    compiled = surfaces["_admit_rt"].lower(
        jax.ShapeDtypeStruct((-(-m // D) * D,), jnp.uint8, sharding=rows),
        h_sds,
        TokenArena(jax.ShapeDtypeStruct((D, S), U32, sharding=rows), groups),
        jax.ShapeDtypeStruct((D * R,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((D * R,), jnp.bool_, sharding=rows)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(groups)
    assert "all-to-all" in text
