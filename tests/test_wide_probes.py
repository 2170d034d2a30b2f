"""Probes as (owner, local) int32 pairs: the split of residues on both
sides of 2^31 and 2^32 (no bit array needed, so the wide path is tested
at m = 7.19e9 on the CPU), and a filter range-sharded over four forced
host devices that holds exactly the host `BloomFilter`'s bits under every
transport, a forced routed overflow and its replay included."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.hash.distributed import probe_owner_local

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _limbs(r: np.ndarray, m: int):
    r = np.asarray(r, np.uint64)
    lo = (r & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    if m < 2**32:
        return jnp.asarray(lo)
    hi = (r >> np.uint64(32)).astype(np.uint32)
    return jnp.stack([jnp.asarray(hi), jnp.asarray(lo)], axis=-1)


@pytest.mark.parametrize("m,D", [(7_188_793_783, 4), (2**34 - 9, 8),
                                 (3 * 2**31 - 5, 3), (2**32 - 1, 3),
                                 (1_437_758_756, 1), (200, 4), (65, 64)])
def test_owner_local_split_is_divmod(m, D):
    m_local = -(-m // D)
    assert m_local < 2**31
    rng = np.random.default_rng(m)
    edges = [e + d for e in range(0, m, m_local) for d in (-1, 0, 1)]
    r = np.concatenate([rng.integers(0, m, 4096, dtype=np.int64),
                        np.clip(edges, 0, m - 1), [0, m - 1]])
    split = jax.jit(lambda x: probe_owner_local(x, m, m_local, D))
    owner, local = (np.asarray(a) for a in split(_limbs(r, m)))
    assert owner.dtype == local.dtype == np.int32
    want = [divmod(int(x), m_local) for x in r]
    assert list(zip(owner.tolist(), local.tolist())) == want
    if m > 2**33:
        assert (r >= 2**32).mean() > 0.3


def test_four_devices_hold_the_bloom_filter_bits():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    code = """
        import numpy as np
        import jax
        import jax.numpy as jnp
        from repro.data.dedup import BloomFilter
        from repro.hash import (DeviceShardedBloom, Hasher, HashSpec,
                                distributed)
        from repro.hash.distributed import probe_owner_local
        from repro.parallel.sharding import data_mesh

        assert jax.device_count() == 4
        rng = np.random.Generator(np.random.Philox(key=np.uint64(0x31DE)))
        def ragged(b, n):
            return [rng.integers(0, 2**32, size=rng.integers(1, n),
                                 dtype=np.uint64).astype(np.uint32)
                    for _ in range(b)]

        def bloom_bits(bf):
            return np.unpackbits(bf.bits.view(np.uint8),
                                 bitorder="little")[:bf.m].astype(bool)

        items, other = ragged(300, 18), ragged(300, 18)
        for transport, tiny in (("all_gather", False), ("routed", False),
                                ("routed", True)):
            if tiny:
                # buckets far below the expected load: every routed call
                # overflows and replays through all_gather
                distributed._CAPACITY_FACTOR = 0.02
                distributed._CAPACITY_SLACK = 0
            bf = BloomFilter(n_items=300, fp_rate=1e-3)
            f = DeviceShardedBloom(n_items=300, fp_rate=1e-3,
                                   probe_transport=transport)
            assert (f.m, f.k, f.n_shards) == (bf.m, bf.k, 4)
            bf.add_batch(items)
            f.add_batch(items)
            # verdicts against the state before the batch, on both sides
            present = bf.contains_batch(other)
            np.testing.assert_array_equal(f.contains_batch(other), present)
            np.testing.assert_array_equal(f.check_and_add_batch(other),
                                          ~present)
            bf.add_batch(other)
            bits = np.asarray(f.bits)
            np.testing.assert_array_equal(bits[:f.m] != 0, bloom_bits(bf))
            assert not bits[f.m:].any()
            assert len(f.bits.addressable_shards) == 4
            if tiny:
                assert f.stats["overflow_fallbacks"] >= 2, f.stats

        # the wide residue through the sharded kernel surface, split by
        # shard on the mesh: exact at m = 7.19e9, D = 4, no bit array
        m, D = 7_188_793_783, 4
        m_local = -(-m // D)
        h = Hasher.from_spec(HashSpec(n_hashes=9, out_bits=64,
                                      variable_length=True, seed=45312),
                             max_len=32)
        sh = h.sharded(data_mesh())
        toks = rng.integers(0, 2**32, size=(64, 32),
                            dtype=np.uint64).astype(np.uint32)
        r = sh.probe_indices(jnp.asarray(toks), m)
        assert r.shape == (64, 9, 2)
        owner, local = (np.asarray(a) for a in jax.jit(
            lambda x: probe_owner_local(x, m, m_local, D))(r))
        want = h.hash_batch(toks, backend="host") % np.uint64(m)
        assert (want >= 2**32).any() and (want < 2**31).any()
        np.testing.assert_array_equal(
            owner.astype(np.int64) * m_local + local, want.astype(np.int64))
        assert set(owner.ravel().tolist()) == {0, 1, 2, 3}
        assert (0 <= local).all() and (local < m_local).all()
        print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")


def test_a_shard_past_the_int32_index_is_refused():
    """m_local >= 2^31 names what would lift the limit; one chip cannot
    hold 7.19e9 bits, four can (built only where there are four)."""
    from repro.hash import DeviceShardedBloom

    with pytest.raises(ValueError, match="packed bits"):
        DeviceShardedBloom(n_items=5 * 10**8, fp_rate=1e-3)
