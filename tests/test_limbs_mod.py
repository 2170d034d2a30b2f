"""Property tests for the 64-mod-m Barrett digit reduction (DESIGN.md §2).

`limbs.mod_u64` / `limbs.mw_mod` and the host twin `hostref.mod_u64_np`
against arbitrary-precision Python-int `%` over random (h, m) pairs plus
the adversarial edges named in the acceptance criteria: m=1, m=2,
m=2^32-1, power-of-two m, and h=2^64-1. Deterministic seeded randomness
(hypothesis is optional on driver images; this suite must always run).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hostref, limbs
from repro.core.limbs import ModPlan

RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x60D)))

EDGE_H = np.array([0, 1, 2, 2**16, 2**31, 2**32 - 1, 2**32, 2**32 + 1,
                   2**48, 2**63, 2**64 - 2, 2**64 - 1], dtype=np.uint64)
EDGE_M = [1, 2, 3, 4, 5, 7, 64, 2**16 - 1, 2**16, 2**16 + 1, 2**31 - 1,
          2**31, 2**31 + 1, 2**32 - 2, 2**32 - 1]


def _split(h):
    return ((h >> np.uint64(32)).astype(np.uint32),
            (h & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _random_h(n):
    return RNG.integers(0, 2**64, size=n, dtype=np.uint64)


@pytest.mark.parametrize("m", EDGE_M)
def test_mod_u64_edge_moduli_vs_python_int(m):
    h = np.concatenate([_random_h(512), EDGE_H])
    plan = ModPlan.for_modulus(m)
    got = np.asarray(limbs.mod_u64(_split(h), plan))
    want = np.asarray([int(x) % m for x in h], np.uint32)
    np.testing.assert_array_equal(got, want)
    assert (got < m).all() or m == 1


def test_mod_u64_random_moduli_vs_python_int():
    h = np.concatenate([_random_h(256), EDGE_H])
    for m in RNG.integers(1, 2**32, size=64):
        plan = ModPlan.for_modulus(int(m))
        got = np.asarray(limbs.mod_u64(_split(h), plan))
        want = np.asarray([int(x) % int(m) for x in h], np.uint32)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m", EDGE_M)
def test_host_twin_bit_exact(m):
    """hostref.mod_u64_np == limbs.mod_u64 == numpy % for the same inputs."""
    h = np.concatenate([_random_h(512), EDGE_H])
    host = hostref.mod_u64_np(h, m)
    np.testing.assert_array_equal(host, (h % np.uint64(m)).astype(np.uint32))
    np.testing.assert_array_equal(
        host, np.asarray(limbs.mod_u64(_split(h), ModPlan.for_modulus(m))))


def test_mod_u64_composes_under_jit_and_vmap():
    h = _random_h(64)
    plan = ModPlan.for_modulus(0xDEADBEEF)
    hi, lo = _split(h)
    want = (h % np.uint64(plan.m)).astype(np.uint32)
    jitted = jax.jit(lambda a, b: limbs.mod_u64((a, b), plan))
    np.testing.assert_array_equal(np.asarray(jitted(hi, lo)), want)
    vm = jax.vmap(lambda a, b: limbs.mod_u64((a, b), plan))
    np.testing.assert_array_equal(np.asarray(vm(jnp.asarray(hi), jnp.asarray(lo))), want)
    # trace-level purity: no host primitives in the jaxpr
    jaxpr = str(jax.make_jaxpr(jitted)(hi, lo))
    for bad in ("callback", "device_get", "infeed"):
        assert bad not in jaxpr


@pytest.mark.parametrize("m", [1, 2, 3, 7, 2**16, 12345, 2**31 + 1, 2**32 - 1])
def test_mw_mod_multiword_vs_python_int(m):
    """4-limb (128-bit) Horner reduction against arbitrary-precision %."""
    vals = ([int(v) for v in _random_h(40)]
            + [int(a) << 64 | int(b) for a, b in
               zip(_random_h(40), _random_h(40))]
            + [0, 1, (1 << 128) - 1, 1 << 96, 1 << 64, (1 << 64) - 1])
    lb = tuple(np.asarray([(v >> (32 * i)) & 0xFFFFFFFF for v in vals],
                          np.uint32) for i in range(4))
    got = np.asarray(limbs.mw_mod(lb, ModPlan.for_modulus(m)))
    np.testing.assert_array_equal(got, np.asarray([v % m for v in vals],
                                                  np.uint32))


def test_mod_plan_validation_and_hashability():
    for bad in (0, -1, 1 << 63, 1 << 64):
        with pytest.raises(ValueError):
            ModPlan.for_modulus(bad)
    # the numpy twin stays the 32-bit reduction
    for bad in (0, -1, 1 << 32, (1 << 32) + 5):
        with pytest.raises(ValueError):
            hostref.mod_u64_np(np.uint64(1), bad)
    # frozen + hashable: usable as a jit static argument / dict key
    a, b = ModPlan.for_modulus(97), ModPlan.for_modulus(97)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    # pow2 plans skip the reciprocal entirely
    p = ModPlan.for_modulus(1024)
    assert p.is_pow2 and (p.mu0, p.mu1, p.mu2) == (0, 0, 0)
    # reciprocal limbs reassemble to floor(2^96/m) + 1
    q = ModPlan.for_modulus(0xDEADBEEF)
    mu = q.mu0 | (q.mu1 << 32) | (q.mu2 << 64)
    assert mu == (1 << 96) // 0xDEADBEEF + 1


@pytest.mark.quality
@pytest.mark.parametrize("m", [3, 4097, 2**32 - 1])
def test_probe_indices_uniform_adversarial_moduli(m):
    """Bucket uniformity of `Hasher.probe_indices` (the fused Barrett mod-m
    epilogue) at adversarial non-pow2 moduli: tiny odd, 2^12+1, and the
    largest 32-bit modulus, where a truncation or reciprocal off-by-one
    would concentrate mass. Fixed-key MULTILINEAR: an odd positional key
    makes the accumulator uniform over random inputs, so residue counts are
    multinomial -- judged by the shared quality-battery chi^2 machinery."""
    from repro.hash import Hasher, HashSpec
    from repro.quality import metrics

    n = 1 << 16
    h = Hasher.from_spec(HashSpec(family="multilinear", n_hashes=2,
                                  out_bits=64, variable_length=False,
                                  seed=0x60D1), max_len=4)
    toks = RNG.integers(0, 2**32, size=(n, 4), dtype=np.uint64
                        ).astype(np.uint32)
    plan = ModPlan.for_modulus(m)
    idx = np.asarray(h.probe_indices(jnp.asarray(toks), plan))
    assert (idx < m).all()
    for k in range(idx.shape[1]):
        if m <= metrics.MAX_EXACT_MOD:
            counts = np.bincount(idx[:, k].astype(np.int64), minlength=m)
            expected = n / m
            df = m - 1
        else:
            nb = 256
            bucket = (idx[:, k].astype(np.uint64) * np.uint64(nb)
                      >> np.uint64(32)).astype(np.int64)
            counts = np.bincount(bucket, minlength=nb)
            expected = metrics.mod_bucket_expected(m, nb, n)
            df = nb - 1
        chi2 = metrics.chi2_stat(counts, expected)
        bound = metrics.chi2_bound(df)
        assert chi2 < bound, f"m={m} k={k}: chi2={chi2} >= {bound}"


@pytest.mark.quality
def test_mod_u64_uniformity_of_uniform_accumulators():
    """`limbs.mod_u64` of uniform 64-bit accumulators is uniform on [0, m)
    up to the 2^64 mod m deficiency -- the distributional contract the
    Bloom probe path (DESIGN.md §2) relies on, checked with the same exact
    expected-count machinery the quality battery uses."""
    from repro.quality import metrics

    n = 1 << 16
    h = _random_h(n)
    for m in (3, 4097):
        r = np.asarray(limbs.mod_u64(_split(h), ModPlan.for_modulus(m)))
        counts = np.bincount(r.astype(np.int64), minlength=m)
        chi2 = metrics.chi2_stat(counts, n / m)
        bound = metrics.chi2_bound(m - 1)
        assert chi2 < bound, f"m={m}: chi2={chi2} >= {bound}"


def test_hasher_probe_indices_matches_bloom_formula():
    """Hasher.probe_indices == the single-device BloomFilter `h % m` on the
    very same uint64 accumulators, for non-pow2 and pow2 m."""
    from repro.hash import Hasher, HashSpec

    h = Hasher.from_spec(HashSpec(family="multilinear", n_hashes=3,
                                  out_bits=64, variable_length=True,
                                  seed=0x60D), max_len=16)
    toks = RNG.integers(0, 2**32, size=(9, 11), dtype=np.uint64
                        ).astype(np.uint32)
    acc = h.hash_batch(toks, backend="host")  # (9, 3) uint64
    h_k = h.with_plan(h.plan.__class__(backend="interpret", block_b=4,
                                       block_n=8))
    for m in (4313, 1, 97, 1024, 2**31 - 1, 2**32 - 1):
        plan = ModPlan.for_modulus(m)
        want = (acc % np.uint64(m)).astype(np.uint32)
        # jnp backend AND the actual kernel body (interpret): both lower
        # probe_indices through the fused mod_m epilogue
        np.testing.assert_array_equal(
            np.asarray(h.probe_indices(jnp.asarray(toks), plan)), want)
        np.testing.assert_array_equal(
            np.asarray(h_k.probe_indices(jnp.asarray(toks), plan)), want)
    with pytest.raises(ValueError, match="out_bits=64"):
        Hasher.from_spec(HashSpec(n_hashes=1, seed=1)).probe_indices(
            jnp.asarray(toks), 97)


# ---------------------------------------------------------------------------
# wide plans: m >= 2^32, the residue as (hi, lo) limbs
# ---------------------------------------------------------------------------

WIDE_M = [2**32 - 1, 2**32, 2**32 + 1, 7_188_793_783, 2**40 + 15, 2**62 + 3]


def _join(r, plan):
    """mod_u64's result as python ints: one uint32, or (hi, lo) limbs."""
    if not plan.wide:
        return [int(v) for v in np.asarray(r)]
    hi, lo = (np.asarray(x).astype(np.uint64) for x in r)
    return [int(v) for v in (hi << np.uint64(32)) | lo]


@pytest.mark.parametrize("m", WIDE_M)
def test_mod_u64_past_2_32_vs_python_int(m):
    """Seeded random 64-bit x and the edges, on both sides of 2^32: below
    it one uint32 residue, from 2^32 on two limbs, every one exact."""
    h = np.concatenate([_random_h(2048), EDGE_H,
                        np.array([m - 1, m, m + 1, 2 * m - 1, 2 * m,
                                  (2**64 - 1) // m * m,
                                  (2**64 - 1) // m * m - 1], np.uint64)])
    plan = ModPlan.for_modulus(m)
    assert plan.wide == (m >= 2**32)
    got = _join(jax.jit(lambda a, b: limbs.mod_u64((a, b), plan))(*_split(h)),
                plan)
    assert got == [int(x) % m for x in h]


@pytest.mark.parametrize("m", WIDE_M)
def test_mod_plan_reciprocal_width(m):
    """M = floor(2^N/m) + 1 with N = 96 below 2^32 and 128 from it on,
    three limbs either way; powers of two carry none."""
    p = ModPlan.for_modulus(m)
    mu = p.mu0 | (p.mu1 << 32) | (p.mu2 << 64)
    if p.is_pow2:
        assert mu == 0
        return
    assert mu == (1 << (128 if p.wide else 96)) // m + 1
    assert max(p.mu0, p.mu1, p.mu2) < 2**32


def test_narrow_plans_keep_one_uint32_residue():
    """Below 2^32 nothing changes: one uint32 array out of `mod_u64` and
    of `Hasher.probe_indices`, and a plan that is not wide."""
    from repro.hash import Hasher, HashSpec

    for m in (97, 1024, 2**31 + 1, 2**32 - 1):
        plan = ModPlan.for_modulus(m)
        assert not plan.wide
        r = limbs.mod_u64(_split(_random_h(8)), plan)
        assert r.dtype == jnp.uint32 and r.shape == (8,)
    h = Hasher.from_spec(HashSpec(n_hashes=3, out_bits=64, seed=3),
                         max_len=8)
    toks = jnp.ones((5, 8), jnp.uint32)
    narrow = h.probe_indices(toks, 2**32 - 1)
    assert narrow.shape == (5, 3) and narrow.dtype == jnp.uint32
    assert h.probe_indices(toks, 2**32 + 15).shape == (5, 3, 2)
    with pytest.raises(ValueError, match="below 2"):
        limbs.mw_mod(_split(_random_h(2)), ModPlan.for_modulus(2**32 + 15))


@pytest.mark.parametrize("family", ["multilinear", "multilinear_hm",
                                    "gf_multilinear", "gf_multilinear_hm"])
@pytest.mark.parametrize("m", [2**32 + 15, 7_188_793_783])
def test_kernel_mod_m_epilogue_past_2_32(family, m):
    """Both fused kernels' `mod_m` epilogues (integer and GF, in
    interpret mode) and the jnp oracle write h64 mod m into the two output
    slots as (hi, lo), equal to `hash_batch(out_bits=64) % m`."""
    from repro.hash import Hasher, HashPlan, HashSpec

    h = Hasher.from_spec(HashSpec(family=family, n_hashes=3, out_bits=64,
                                  variable_length=True, seed=0x3D4),
                         max_len=40)
    rows = [RNG.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
            for n in (1, 7, 16, 33, 3, 12)]
    acc = h.hash_batch(rows, backend="host")  # (6, 3) uint64
    want = acc % np.uint64(m)
    toks = np.zeros((6, 40), np.uint32)
    for i, row in enumerate(rows):
        toks[i, :len(row)] = row
    lens = jnp.asarray([len(r) for r in rows], jnp.int32)
    for plan in (HashPlan(backend="jnp"),
                 HashPlan(backend="interpret", block_b=8, block_n=16)):
        out = np.asarray(h.with_plan(plan).probe_indices(
            jnp.asarray(toks), m, lens)).astype(np.uint64)
        assert out.shape == (6, 3, 2)
        got = (out[..., 0] << np.uint64(32)) | out[..., 1]
        np.testing.assert_array_equal(got, want, plan.backend)
    # residues past 2^32 (about 40% at 7.19e9; at 2^32 + 15 one in 2^28),
    # and a reduction that is no truncation to 32 bits
    assert (want >= 2**32).any() or m < 2**33
    assert (want != acc % np.uint64(2**32)).any()
