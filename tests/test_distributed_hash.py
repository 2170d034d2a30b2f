"""repro.hash.distributed: ShardedHasher / DeviceShardedBloom.

The D=1 contract runs in-process (the CPU CI runner IS the degenerate mesh:
same shard_map code path, size-1 collectives) and pins bit-identity against
the single-device engine. True multi-device behaviour runs in a SUBPROCESS
with 8 fake host devices (the repo's dry-run contract: only a subprocess
pins a device count).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom, Hasher, HashSpec, ShardedHasher
from repro.parallel.sharding import data_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.Generator(np.random.Philox(key=np.uint64(0xD15)))

FAMILIES = ["multilinear", "multilinear_2x2", "multilinear_hm"]


def _toks(b, n):
    return RNG.integers(0, 2**32, size=(b, n), dtype=np.uint64).astype(np.uint32)


def _ragged(b, max_n):
    return [RNG.integers(0, 2**32, size=RNG.integers(1, max_n),
                         dtype=np.uint64).astype(np.uint32) for _ in range(b)]


def _assert_pure(fn, *args):
    """Trace-level proof of zero host syncs (same check as test_hasher)."""
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    for bad in ("callback", "host_callback", "device_get", "infeed"):
        assert bad not in jaxpr, f"host primitive {bad!r} in jaxpr"


# ---------------------------------------------------------------------------
# ShardedHasher, mesh of size 1 (the CI pin: acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("variable_length", [True, False])
def test_d1_hash_batch_bit_identical(family, variable_length):
    spec = HashSpec(family=family, n_hashes=3,
                    variable_length=variable_length, seed=0xD15)
    h = Hasher.from_spec(spec, max_len=24)
    sh = h.sharded()  # live device set: size-1 mesh on the CI runner
    toks = _toks(7, 17)  # 7 rows: exercises the pad-to-multiple-of-D path
    np.testing.assert_array_equal(sh.hash_batch(toks),
                                  h.hash_batch(toks, backend="host"))


def test_d1_pure_call_and_shard_ids_bit_identical():
    spec = HashSpec(family="multilinear_hm", n_hashes=2, seed=0xD16)
    h = Hasher.from_spec(spec, max_len=24)
    sh = h.sharded()
    toks = jnp.asarray(_toks(6, 17))
    np.testing.assert_array_equal(np.asarray(sh(toks)), np.asarray(h(toks)))
    np.testing.assert_array_equal(np.asarray(sh.shard_ids(toks, 13)),
                                  np.asarray(h.shard_ids(toks, 13)))
    _assert_pure(lambda t: sh(t), toks)
    _assert_pure(lambda t: sh.shard_ids(t, 13), toks)


def test_d1_probe_indices_bit_identical():
    """ShardedHasher.probe_indices == Hasher.probe_indices (the fused
    Barrett mod-m epilogue) at adversarial non-pow2 and pow2 moduli, and
    stays host-primitive-free."""
    spec = HashSpec(family="multilinear", n_hashes=3, out_bits=64,
                    seed=0xD19)
    h = Hasher.from_spec(spec, max_len=24)
    sh = h.sharded()
    toks = jnp.asarray(_toks(7, 17))  # non-multiple of D: pad path
    for m in (3, 4097, 1024, 2**32 - 1):
        np.testing.assert_array_equal(
            np.asarray(sh.probe_indices(toks, m)),
            np.asarray(h.probe_indices(toks, m)))
    _assert_pure(lambda t: sh.probe_indices(t, 4097), toks)


def test_d1_ragged_and_lengths():
    spec = HashSpec(n_hashes=2, variable_length=True, seed=0xD17)
    h = Hasher.from_spec(spec, max_len=16)
    sh = h.sharded()
    rows = _ragged(5, 12)
    np.testing.assert_array_equal(sh.hash_batch(rows),
                                  h.hash_batch(rows, backend="host"))
    # explicit in-graph lengths == ragged host batch
    toks = _toks(5, 12)
    lens = np.asarray([0, 3, 12, 7, 1])
    got = np.asarray(sh(jnp.asarray(toks), jnp.asarray(lens)))
    want = h.hash_batch([toks[i, : lens[i]] for i in range(5)],
                        backend="host")
    np.testing.assert_array_equal(got, want)


def test_d1_out_bits():
    h64 = Hasher.from_spec(HashSpec(n_hashes=2, out_bits=64, seed=0xD18),
                           max_len=16)
    sh64 = h64.sharded()
    toks = _toks(4, 9)
    np.testing.assert_array_equal(sh64.hash_batch(toks),
                                  h64.hash_batch(toks, backend="host"))
    # 64-bit override from a 32-bit spec widens output, not keys
    h32 = Hasher.from_spec(HashSpec(n_hashes=2, seed=0xD19), max_len=16)
    sh32 = h32.sharded()
    np.testing.assert_array_equal(sh32.hash_batch(toks, out_bits=64),
                                  h32.hash_batch(toks, backend="host",
                                                 out_bits=64))
    np.testing.assert_array_equal(sh32.hash_batch(toks),
                                  h32.hash_batch(toks, backend="host"))


def test_sharded_capacity_growth():
    h = Hasher.from_spec(HashSpec(seed=0xD1A), max_len=4)
    sh = h.sharded()
    short = _toks(2, 3)
    before = sh.hash_batch(short)
    long = _toks(3, 8 * int(h.capacity))
    np.testing.assert_array_equal(
        sh.hash_batch(long), sh.hasher.hash_batch(long, backend="host"))
    # growth extended the same Philox streams: short-row hashes unchanged
    np.testing.assert_array_equal(sh.hash_batch(short), before)


def test_sharded_requires_axis():
    h = Hasher.from_spec(HashSpec(seed=1), max_len=8)
    with pytest.raises(ValueError, match="no 'rows'"):
        ShardedHasher(h, axis="rows")


# ---------------------------------------------------------------------------
# DeviceShardedBloom vs single-device BloomFilter (acceptance criterion)
# ---------------------------------------------------------------------------

def test_sharded_bloom_matches_single_device_decisions():
    """Same (m, k, seed) and the same global `h mod m` probe formula =>
    decisions are bit-identical by construction, pinned on a fixed key set
    (deterministic hashing: no flake margin needed)."""
    items, other = _ragged(400, 20), _ragged(400, 20)
    bf = BloomFilter(n_items=400, fp_rate=1e-3)
    dsb = DeviceShardedBloom(n_items=400, fp_rate=1e-3)
    assert (dsb.m, dsb.k) == (bf.m, bf.k)
    bf.add_batch(items)
    dsb.add_batch(items)
    # no false negatives, ever
    assert dsb.contains_batch(items).all()
    # decision-for-decision match on a disjoint probe set (incl. any FPs)
    np.testing.assert_array_equal(dsb.contains_batch(other),
                                  bf.contains_batch(other))


def test_sharded_bloom_probes_in_graph_zero_syncs_one_psum():
    """Acceptance criterion: `add` lowers to a graph with NO host primitives
    and ZERO psums; `contains`/fused admission carry exactly ONE psum -- on
    BOTH in-graph transports. The probe collective (all_gather, or the
    routed all_to_all) replaces the old host round-trip -- device-to-device,
    not a sync. Routed surfaces carry exactly one all_to_all and NO
    all_gather (the bytes claim of DESIGN.md §7). The same holds where a
    call stages one bucket and where it stages two row groups, each
    hashed on its own."""
    dsb = DeviceShardedBloom(n_items=128, fp_rate=1e-2)
    one, grouped = dsb._stage(_ragged(9, 12)), dsb._stage(_ragged(70, 40))
    assert len(one.tokens.groups) == 1 and len(grouped.tokens.groups) == 2
    for st in (one, grouped):
        args = (dsb.bits, dsb.sharded.hasher, *st[:3])
        j_add = str(jax.make_jaxpr(dsb._add_dev)(*args))
        j_con = str(jax.make_jaxpr(dsb._contains_dev)(*args))
        j_adm = str(jax.make_jaxpr(dsb._admit_dev)(*args))
        j_add_rt = str(jax.make_jaxpr(dsb._add_rt)(*args))
        j_con_rt = str(jax.make_jaxpr(dsb._contains_rt)(*args))
        j_adm_rt = str(jax.make_jaxpr(dsb._admit_rt)(*args))
        for jaxpr in (j_add, j_con, j_adm, j_add_rt, j_con_rt, j_adm_rt):
            for bad in ("callback", "host_callback", "device_get", "infeed"):
                assert bad not in jaxpr, f"host primitive {bad!r} in jaxpr"
        assert j_add.count("psum") == 0 and j_add_rt.count("psum") == 0
        assert j_con.count("psum") == 1 and j_con_rt.count("psum") == 1
        assert j_adm.count("psum") == 1 and j_adm_rt.count("psum") == 1
        for jaxpr in (j_add_rt, j_con_rt, j_adm_rt):
            assert jaxpr.count("all_to_all") == 1
            assert "all_gather" not in jaxpr


def test_sharded_bloom_in_graph_matches_host_mod_path():
    """A/B: the in-graph Barrett reduction lights exactly the bits of the
    host `h % m` formula (numpy over the host hash) and decides as the
    single-device filter, before and after a fused admission."""
    items, other = _ragged(200, 16), _ragged(200, 16)
    dev = DeviceShardedBloom(n_items=200, fp_rate=1e-3,
                             probe_transport="all_gather")
    bf = BloomFilter(n_items=200, fp_rate=1e-3)
    assert dev.plan.m == dev.m and not dev.plan.is_pow2

    def host_bits(batches):
        want = np.zeros(dev.m, bool)
        for b in batches:
            want[dev.sharded.hasher.hash_batch(b, backend="host")
                 % np.uint64(dev.m)] = True
        return want

    dev.add_batch(items)
    bf.add_batch(items)
    np.testing.assert_array_equal(np.asarray(dev.bits)[:dev.m] != 0,
                                  host_bits([items]))
    np.testing.assert_array_equal(dev.contains_batch(other),
                                  bf.contains_batch(other))
    np.testing.assert_array_equal(dev.check_and_add_batch(other),
                                  ~bf.contains_batch(other))
    np.testing.assert_array_equal(np.asarray(dev.bits)[:dev.m] != 0,
                                  host_bits([items, other]))


def test_sharded_bloom_dense_input_and_row_bucketing():
    """Dense (B, N) input (no ragged lengths) through the in-graph path,
    with B chosen to exercise the pad-to-D-multiple + pow2 row bucket."""
    toks = _toks(7, 13)
    bf = BloomFilter(n_items=64, fp_rate=1e-2)
    dsb = DeviceShardedBloom(n_items=64, fp_rate=1e-2)
    bf.add_batch(toks)
    dsb.add_batch(toks)
    assert dsb.contains_batch(toks).all()
    probe = _toks(11, 13)
    np.testing.assert_array_equal(dsb.contains_batch(probe),
                                  bf.contains_batch(probe))


def test_sharded_bloom_fused_admission():
    items = _ragged(128, 16)
    dsb = DeviceShardedBloom(n_items=256, fp_rate=1e-3)
    assert dsb.check_and_add_batch(items).all()       # fresh keys admit
    assert not dsb.check_and_add_batch(items).any()   # replay rejects
    # single-item surface agrees with the batch surface
    assert np.atleast_1d(items[0]) in dsb
    dsb.add(np.asarray([1, 2, 3], np.uint32))
    assert np.asarray([1, 2, 3], np.uint32) in dsb


def test_sharded_bloom_empty_batches():
    dsb = DeviceShardedBloom(n_items=64, fp_rate=1e-2)
    dsb.add_batch([])
    assert dsb.contains_batch([]).shape == (0,)
    assert dsb.check_and_add_batch([]).shape == (0,)


def test_owner_shards_lemire_routing():
    dsb = DeviceShardedBloom(n_items=64, fp_rate=1e-2)
    ow = dsb.owner_shards(_ragged(50, 8))
    assert ow.shape == (50,)
    assert ((ow >= 0) & (ow < dsb.n_shards)).all()


# ---------------------------------------------------------------------------
# consumers: mesh paths keep decisions bit-identical
# ---------------------------------------------------------------------------

def test_exact_dedup_mesh_path_matches():
    from repro.data.dedup import ExactDedup

    docs = _ragged(64, 12) * 2  # force duplicates
    plain, meshed = ExactDedup(), ExactDedup(mesh=data_mesh(1))
    np.testing.assert_array_equal(plain.check_and_add_batch(docs),
                                  meshed.check_and_add_batch(docs))


def test_pipeline_mesh_path_matches():
    from repro.data.pipeline import HashPipeline, PipelineConfig

    cfg = PipelineConfig(seq_len=8, batch_size=2, eval_pct=10, n_shards=4,
                         shard_id=1)
    docs = _ragged(80, 12)
    plain = HashPipeline(cfg)
    meshed = HashPipeline(cfg, mesh=data_mesh(1))
    assert plain.admit_batch(docs) == meshed.admit_batch(docs)
    assert plain.stats == meshed.stats


# ---------------------------------------------------------------------------
# true multi-device: 8 fake host devices in a subprocess
# ---------------------------------------------------------------------------

def test_multi_device_bit_identity_and_bloom():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.data.dedup import BloomFilter
        from repro.hash import DeviceShardedBloom, Hasher, HashSpec
        rng = np.random.Generator(np.random.Philox(key=np.uint64(0xD8)))
        toks = rng.integers(0, 2**32, size=(21, 13), dtype=np.uint64).astype(np.uint32)
        h = Hasher.from_spec(HashSpec(family="multilinear_hm", n_hashes=3,
                                      seed=0xD8), max_len=16)
        sh = h.sharded()
        assert sh.n_shards == 8, sh.n_shards
        np.testing.assert_array_equal(sh.hash_batch(toks),
                                      h.hash_batch(toks, backend="host"))
        np.testing.assert_array_equal(np.asarray(sh(jnp.asarray(toks))),
                                      np.asarray(h(jnp.asarray(toks))))
        items = [rng.integers(0, 2**32, size=rng.integers(1, 20),
                              dtype=np.uint64).astype(np.uint32)
                 for _ in range(300)]
        other = [rng.integers(0, 2**32, size=rng.integers(1, 20),
                              dtype=np.uint64).astype(np.uint32)
                 for _ in range(300)]
        bf = BloomFilter(n_items=300, fp_rate=1e-3)
        dsb = DeviceShardedBloom(n_items=300, fp_rate=1e-3)
        assert dsb.n_shards == 8
        bf.add_batch(items); dsb.add_batch(items)
        assert dsb.contains_batch(items).all()
        np.testing.assert_array_equal(dsb.contains_batch(other),
                                      bf.contains_batch(other))
        loads = np.bincount(dsb.owner_shards(items), minlength=8)
        assert (loads > 0).all(), loads  # Lemire routing spreads the load
        # both transports == the host filter on a REAL 8-way mesh:
        # identical bits, identical fused-admission verdicts (dsb is the
        # default "routed" transport)
        def bloom_bits(bf):
            return np.unpackbits(bf.bits.view(np.uint8),
                                 bitorder="little")[:bf.m].astype(bool)
        gathered = DeviceShardedBloom(n_items=300, fp_rate=1e-3,
                                      probe_transport="all_gather")
        gathered.add_batch(items)
        np.testing.assert_array_equal(np.asarray(dsb.bits)[:dsb.m] != 0,
                                      bloom_bits(bf))
        np.testing.assert_array_equal(np.asarray(dsb.bits),
                                      np.asarray(gathered.bits))
        adm = dsb.check_and_add_batch(other)
        np.testing.assert_array_equal(adm, ~bf.contains_batch(other))
        np.testing.assert_array_equal(adm, gathered.check_and_add_batch(other))
        bf.add_batch(other)
        np.testing.assert_array_equal(np.asarray(dsb.bits)[:dsb.m] != 0,
                                      bloom_bits(bf))
        np.testing.assert_array_equal(np.asarray(dsb.bits),
                                      np.asarray(gathered.bits))
        # Barrett digit reduction under shard_map: edge moduli incl. m=1,
        # pow2 and 2^32-1 stay bit-identical to numpy's uint64 %
        from jax.sharding import PartitionSpec as P
        from repro.core.limbs import ModPlan, mod_u64
        from repro.parallel.sharding import data_mesh
        hs = rng.integers(0, 2**64, size=64, dtype=np.uint64)
        hs[:3] = [0, 2**64 - 1, 2**32]
        hi = jnp.asarray((hs >> np.uint64(32)).astype(np.uint32))
        lo = jnp.asarray((hs & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        mesh = data_mesh()
        for m in (1, 2, 97, 1024, 2**31 + 1, 2**32 - 1):
            plan = ModPlan.for_modulus(m)
            fn = jax.jit(jax.shard_map(
                lambda a, b: mod_u64((a, b), plan), mesh=mesh,
                in_specs=(P("data"), P("data")), out_specs=P("data")))
            np.testing.assert_array_equal(
                np.asarray(fn(hi, lo)), (hs % np.uint64(m)).astype(np.uint32))
        print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
