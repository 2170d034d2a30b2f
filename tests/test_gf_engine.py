"""GF(2^32) carry-less engine (DESIGN.md §11): arithmetic property tests
against python-int ground truth, cross-backend bit-identity of the fused
multi-hash kernel, and the `HashSpec(family="gf_multilinear")` promotion
(pure-JAX call path, probe indices, sharding -- D=1 in-process, D=4 in a
subprocess, following the repo's device-count pin contract).

Style follows tests/test_limbs_mod.py: deterministic seeded randomness plus
the named adversarial operands (0, 1, 2^32-1, single-bit, dense); hypothesis
is optional on driver images, so this suite must always run.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf as gf_core
from repro.core import hostref, limbs
from repro.hash import Hasher, HashSpec
from repro.kernels import ref as kref
from repro.kernels.gf_multihash import _clmul_tile, gf_multihash_blocks

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x6F)))

# adversarial 32-bit operands: zero, one, all-ones, every single bit, and a
# dense random tail (clmul/Barrett failures cluster at shift boundaries)
EDGE_OPS = np.concatenate([
    np.array([0, 1, 2**32 - 1, 0xC5, 0x80000000], np.uint64),
    np.uint64(1) << np.arange(32, dtype=np.uint64),
    RNG.integers(0, 2**32, size=27, dtype=np.uint64),
]).astype(np.uint32)

GF_FAMILIES = ["gf_multilinear", "gf_multilinear_hm"]
EDGE_M = [1, 3, 97, 1024, 4313, 2**31 - 1, 2**32 - 1]


def _toks(b, n):
    return RNG.integers(0, 2**32, size=(b, n), dtype=np.uint64).astype(
        np.uint32)


def _assert_pure(fn, *args):
    """Trace-level proof of zero host syncs (same check as test_hasher)."""
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    for bad in ("callback", "host_callback", "device_get", "infeed"):
        assert bad not in jaxpr, f"host primitive {bad!r} in jaxpr"


# ---------------------------------------------------------------------------
# carry-less arithmetic: every implementation vs python-int ground truth
# ---------------------------------------------------------------------------

def test_clmul32_matches_clmul_ref_on_edges():
    a = np.repeat(EDGE_OPS, len(EDGE_OPS))
    b = np.tile(EDGE_OPS, len(EDGE_OPS))
    hi, lo = map(np.asarray, gf_core.clmul32(jnp.asarray(a), jnp.asarray(b)))
    got = (hi.astype(np.uint64) << 32) | lo
    want = np.asarray([gf_core.clmul_ref(int(x), int(y))
                       for x, y in zip(a, b)], np.uint64)
    np.testing.assert_array_equal(got, want)


def test_clmul_tile_and_numpy_twin_match_clmul_ref():
    """The kernel's plane decomposition (`_clmul_tile`) and the host twin
    (`hostref._clmul32_np`) agree with the bit-at-a-time ground truth."""
    n = len(EDGE_OPS)
    a = np.repeat(EDGE_OPS, n).reshape(n, n)
    b = np.tile(EDGE_OPS, n).reshape(n, n)
    t_hi, t_lo = map(np.asarray, _clmul_tile(jnp.asarray(a), jnp.asarray(b)))
    tile = (t_hi.astype(np.uint64) << 32) | t_lo
    host = hostref._clmul32_np(a, b)
    want = np.asarray([[gf_core.clmul_ref(int(x), int(y)) for x, y in
                        zip(ra, rb)] for ra, rb in zip(a, b)], np.uint64)
    np.testing.assert_array_equal(tile, want)
    np.testing.assert_array_equal(host, want)


def test_clmul32_with_poly_matches_ref():
    got_hi, got_lo = map(np.asarray,
                         gf_core.clmul32_with_poly(jnp.asarray(EDGE_OPS)))
    got = (got_hi.astype(np.uint64) << 32) | got_lo
    want = np.asarray([gf_core.clmul_ref(int(x), gf_core.POLY_FULL_INT)
                       for x in EDGE_OPS], np.uint64)
    np.testing.assert_array_equal(got, want)


def test_barrett_reduce_matches_poly_mod_ref():
    """Barrett over the full adversarial 63-bit accumulator grid: every
    (hi, lo) edge pair plus random accumulators, vs GF(2)[x] long division.
    hi < 2^31 (the carry-less 32x32 product is 63-bit)."""
    hi31 = (EDGE_OPS >> np.uint32(1)).astype(np.uint32)
    hi = np.repeat(hi31, len(EDGE_OPS))
    lo = np.tile(EDGE_OPS, len(EDGE_OPS))
    got = np.asarray(gf_core.barrett_reduce(jnp.asarray(hi), jnp.asarray(lo)))
    acc = (hi.astype(np.uint64) << 32) | lo
    want = np.asarray([gf_core.poly_mod_ref(int(q)) for q in acc], np.uint32)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(hostref._gf_barrett_np(acc), want)


def test_h64_surface_is_bijective_with_accumulator():
    """h64 = (hash32 << 32) | acc_hi determines the raw 63-bit accumulator:
    the Barrett correction depends on the hi limb alone, so
    acc_lo = hash32 ^ f(acc_hi) inverts the packing (DESIGN.md §11)."""
    hi = (RNG.integers(0, 2**31, size=256, dtype=np.uint64)).astype(np.uint32)
    lo = RNG.integers(0, 2**32, size=256, dtype=np.uint64).astype(np.uint32)
    h32 = np.asarray(gf_core.barrett_reduce(jnp.asarray(hi), jnp.asarray(lo)))
    f = np.asarray(gf_core.barrett_reduce(jnp.asarray(hi),
                                          jnp.zeros_like(jnp.asarray(lo))))
    np.testing.assert_array_equal(h32 ^ f, lo)


# ---------------------------------------------------------------------------
# fused kernel: cross-backend bit-identity incl. ragged + mod_m
# ---------------------------------------------------------------------------

def _engine_case(family, variable_length, B=12, N=10, K=3):
    """Block-aligned engine operands + the per-row python-int ground truth."""
    toks = _toks(B, N).astype(np.uint32)
    key_lo = _toks(K, N)
    m1 = np.zeros((K, 2), np.uint32)
    m1[:, 1] = _toks(1, K)[0]
    m1[:, 0] = _toks(1, K)[0]  # hi limb must be IGNORED by the gf engine
    if variable_length:
        lens_raw = RNG.integers(0, N - 1, size=B).astype(np.int64)
        code = lens_raw.astype(np.int32)
    else:
        lens_raw = None
        code = np.full(B, -(N + 1), np.int32)

    hm = family.endswith("_hm")
    want = np.zeros((B, K), np.uint64)
    for b in range(B):
        if variable_length:
            L = int(code[b])
            row = list(map(int, toks[b, :L])) + [1]
            live = (L + 1) + ((L + 1) & 1)  # keys live through even(L+1)
            row += [0] * (live - len(row))
        else:
            row = list(map(int, toks[b]))
        for k in range(K):
            keys = [int(m1[k, 1])] + list(map(int, key_lo[k, :len(row)]))
            want[b, k] = gf_core.gf_h64_ref(row, keys, hm=hm)
    return toks, key_lo, code, m1, want


@pytest.mark.parametrize("family", GF_FAMILIES)
@pytest.mark.parametrize("variable_length", [False, True])
def test_kernel_oracle_host_bit_identical(family, variable_length):
    toks, key_lo, code, m1, want = _engine_case(family, variable_length)
    # interpret kernel at an odd block boundary (tiles straddle rows/lanes)
    interp = np.asarray(gf_multihash_blocks(
        jnp.asarray(toks), jnp.asarray(key_lo), jnp.asarray(code),
        jnp.asarray(m1), family=family, block_b=4, block_n=2,
        interpret=True))
    oracle = np.asarray(kref.gf_multihash_ref(
        jnp.asarray(toks), jnp.asarray(key_lo), jnp.asarray(code),
        jnp.asarray(m1), family=family))
    np.testing.assert_array_equal(interp, oracle)
    got = (interp[:, :, 0].astype(np.uint64) << 32) | interp[:, :, 1]
    np.testing.assert_array_equal(got, want)
    # independent vectorized host twin (keys32 carries m1 at column 0)
    keys32 = np.concatenate([m1[:, 1:2], key_lo], axis=1)
    host = hostref.gf_multilinear_multi_np(toks, code, keys32, family=family)
    np.testing.assert_array_equal(host, want)


@pytest.mark.parametrize("family", GF_FAMILIES)
@pytest.mark.parametrize("m", EDGE_M)
def test_kernel_mod_m_epilogue(family, m):
    """With mod_m: slot 0 == h64 % m (python-int), slot 1 == hash32."""
    toks, key_lo, code, m1, want = _engine_case(family, True)
    plan = limbs.ModPlan.for_modulus(m)
    out = np.asarray(gf_multihash_blocks(
        jnp.asarray(toks), jnp.asarray(key_lo), jnp.asarray(code),
        jnp.asarray(m1), family=family, block_b=4, block_n=2,
        interpret=True, mod_m=plan))
    np.testing.assert_array_equal(out[:, :, 0],
                                  (want % np.uint64(m)).astype(np.uint32))
    np.testing.assert_array_equal(out[:, :, 1],
                                  (want >> np.uint64(32)).astype(np.uint32))


# ---------------------------------------------------------------------------
# HashSpec promotion: the engine surface end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", GF_FAMILIES)
@pytest.mark.parametrize("variable_length", [False, True])
def test_hash_batch_backends_bit_identical(family, variable_length):
    spec = HashSpec(family=family, n_hashes=3, out_bits=64,
                    variable_length=variable_length, seed=0x6F)
    h = Hasher.from_spec(spec, max_len=24)
    items = ([_toks(1, int(n))[0] for n in RNG.integers(1, 20, size=9)]
             if variable_length else _toks(9, 16))
    host = h.hash_batch(items, backend="host")
    for backend in ("jnp", "interpret"):
        np.testing.assert_array_equal(h.hash_batch(items, backend=backend),
                                      host)
    # hi 32 bits ARE the finished hash (paper convention, both out_bits)
    np.testing.assert_array_equal(
        h.hash_batch(items, backend="jnp", out_bits=32),
        (host >> np.uint64(32)).astype(np.uint32))


@pytest.mark.parametrize("family", GF_FAMILIES)
def test_pure_call_jit_vmap_and_no_host_syncs(family):
    spec = HashSpec(family=family, n_hashes=2, out_bits=64, seed=0x6F)
    h = Hasher.from_spec(spec, max_len=8)
    toks = jnp.asarray(_toks(6, 8))
    _assert_pure(lambda hs, t: hs(t), h, toks)
    out = np.asarray(h(toks))
    np.testing.assert_array_equal(np.asarray(jax.jit(lambda hs, t: hs(t))(
        h, toks)), out)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(lambda t: h(t))(toks)), out)
    # hash_batch's u64 packing is the same surface as the pure call's limbs
    h64 = h.hash_batch(np.asarray(toks))
    np.testing.assert_array_equal(
        (out[:, :, 0].astype(np.uint64) << 32) | out[:, :, 1], h64)


def test_probe_indices_match_host_mod_and_stay_pure():
    spec = HashSpec(family="gf_multilinear", n_hashes=3, out_bits=64,
                    variable_length=True, seed=0x6F)
    h = Hasher.from_spec(spec, max_len=16)
    toks = jnp.asarray(_toks(10, 12))
    h64 = h.hash_batch(np.asarray(toks), backend="host")
    for m in EDGE_M:
        plan = limbs.ModPlan.for_modulus(m)
        _assert_pure(lambda hs, t, p=plan: hs.probe_indices(t, p), h, toks)
        idx = np.asarray(jax.jit(
            lambda hs, t, p=plan: hs.probe_indices(t, p))(h, toks))
        np.testing.assert_array_equal(idx, (h64 % np.uint64(m)).astype(
            np.uint32))


@pytest.mark.parametrize("family", GF_FAMILIES)
def test_d1_sharded_bit_identical(family):
    spec = HashSpec(family=family, n_hashes=2, out_bits=64,
                    variable_length=True, seed=0x6F)
    h = Hasher.from_spec(spec, max_len=24)
    sh = h.sharded()  # size-1 mesh on the CI runner: same shard_map path
    toks = _toks(7, 17)
    np.testing.assert_array_equal(sh.hash_batch(toks),
                                  h.hash_batch(toks, backend="host"))
    np.testing.assert_array_equal(np.asarray(sh(jnp.asarray(toks))),
                                  np.asarray(h(jnp.asarray(toks))))
    plan = limbs.ModPlan.for_modulus(4313)
    np.testing.assert_array_equal(
        np.asarray(sh.probe_indices(jnp.asarray(toks), plan)),
        np.asarray(jax.jit(lambda hs, t: hs.probe_indices(t, plan))(
            h, jnp.asarray(toks))))


def test_bloom_filter_gf_family_round_trip():
    from repro.data.dedup import BloomFilter

    bf = BloomFilter(n_items=200, fp_rate=1e-3, family="gf_multilinear")
    items = [_toks(1, int(n))[0] for n in RNG.integers(1, 16, size=200)]
    other = [_toks(1, int(n))[0] for n in RNG.integers(1, 16, size=200)]
    bf.add_batch(items)
    assert bf.contains_batch(items).all()
    assert all(it in bf for it in items[:16])
    # FP rate sanity at 1e-3 design point: a few hits at most out of 200
    assert bf.contains_batch(other).sum() <= 5


# ---------------------------------------------------------------------------
# true multi-device: GF spec on 4 fake host devices (subprocess pin)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multi_device_gf_bit_identity_and_bloom():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.limbs import ModPlan
        from repro.data.dedup import BloomFilter
        from repro.hash import DeviceShardedBloom, Hasher, HashSpec
        rng = np.random.Generator(np.random.Philox(key=np.uint64(0x6FD)))
        h = Hasher.from_spec(HashSpec(family="gf_multilinear", n_hashes=3,
                                      out_bits=64, variable_length=True,
                                      seed=0x6FD), max_len=20)
        sh = h.sharded()
        assert sh.n_shards == 4, sh.n_shards
        toks = rng.integers(0, 2**32, size=(21, 13),
                            dtype=np.uint64).astype(np.uint32)
        np.testing.assert_array_equal(sh.hash_batch(toks),
                                      h.hash_batch(toks, backend="host"))
        np.testing.assert_array_equal(np.asarray(sh(jnp.asarray(toks))),
                                      np.asarray(h(jnp.asarray(toks))))
        for m in (3, 4313, 2**32 - 1):
            plan = ModPlan.for_modulus(m)
            np.testing.assert_array_equal(
                np.asarray(sh.probe_indices(jnp.asarray(toks), plan)),
                (h.hash_batch(toks, backend="host")
                 % np.uint64(m)).astype(np.uint32))
        items = [rng.integers(0, 2**32, size=rng.integers(1, 18),
                              dtype=np.uint64).astype(np.uint32)
                 for _ in range(250)]
        other = [rng.integers(0, 2**32, size=rng.integers(1, 18),
                              dtype=np.uint64).astype(np.uint32)
                 for _ in range(250)]
        bf = BloomFilter(n_items=250, fp_rate=1e-3, family="gf_multilinear")
        bf.add_batch(items)
        blooms = [DeviceShardedBloom(n_items=250, fp_rate=1e-3,
                                     family="gf_multilinear",
                                     probe_transport=pt)
                  for pt in ("routed", "all_gather")]
        ref_bits = np.unpackbits(bf.bits.view(np.uint8),
                                 bitorder="little")[:bf.m].astype(bool)
        for dsb in blooms:
            assert dsb.n_shards == 4
            dsb.add_batch(items)
            assert dsb.contains_batch(items).all()
            np.testing.assert_array_equal(dsb.contains_batch(other),
                                          bf.contains_batch(other))
            np.testing.assert_array_equal(np.asarray(dsb.bits)[:dsb.m] != 0,
                                          ref_bits)
        np.testing.assert_array_equal(np.asarray(blooms[0].bits),
                                      np.asarray(blooms[1].bits))
        print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
