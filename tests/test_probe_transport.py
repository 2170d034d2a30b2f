"""The two probe transports, routed and all_gather: A/B equivalence with
the single-device `BloomFilter` and the numpy `h mod m` positions, the
transport check, the bucket-overflow repair, and the consumers' default
filters (service backends, ExactDedup, reconcile convergence).

D=1 contracts run in-process (the degenerate mesh runs the SAME routed
all_to_all code path); true multi-device behaviour (D=4) runs in a
subprocess with fake host devices, per the repo's device-count contract.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom, distributed
from repro.hash.distributed import _capacity
from repro.parallel.sharding import data_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x9702)))

TRANSPORTS = ["all_gather", "routed"]


def _ragged(b, max_n):
    return [RNG.integers(0, 2**32, size=RNG.integers(1, max_n),
                         dtype=np.uint64).astype(np.uint32) for _ in range(b)]


def _bloom_bits(bf) -> np.ndarray:
    """The host filter's m bits, one bool each."""
    return np.unpackbits(bf.bits.view(np.uint8),
                         bitorder="little")[:bf.m].astype(bool)


@pytest.fixture
def tiny_capacity(monkeypatch):
    """One probe a routed bucket: every routed launch overflows."""
    monkeypatch.setattr(distributed, "_CAPACITY_FACTOR", 1e-9)
    monkeypatch.setattr(distributed, "_CAPACITY_SLACK", 0)


# ---------------------------------------------------------------------------
# the transport check and the bucket capacity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", ["host", "carrier_pigeon", 7])
def test_probe_transport_validation(bad):
    with pytest.raises(ValueError, match="'routed' nor 'all_gather'"):
        DeviceShardedBloom(n_items=64, probe_transport=bad)
    with pytest.raises(TypeError):  # the boolean it replaced is gone
        DeviceShardedBloom(n_items=64, in_graph_mod=True)


def test_probe_transport_capacity(monkeypatch):
    # never exceeds the probe count, never below 1
    assert _capacity(100, 1) == 100
    # headroom: cap * D covers the probes with the factor to spare
    cap = _capacity(4096, 4)
    assert 4096 * 1.25 / 4 <= cap <= 4096
    assert cap == 1280 + 16
    # the factor >= 1 makes D=1 structurally overflow-free
    assert _capacity(7, 1) == 7
    # the test seam: shrunk constants leave one slot a bucket
    monkeypatch.setattr(distributed, "_CAPACITY_FACTOR", 1e-9)
    monkeypatch.setattr(distributed, "_CAPACITY_SLACK", 0)
    assert _capacity(100, 4) == 1


# ---------------------------------------------------------------------------
# D=1 A/B: every transport == single-device BloomFilter, identical bits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport", TRANSPORTS)
def test_transport_matches_bloom_filter(transport):
    items, other = _ragged(300, 16), _ragged(300, 16)
    bf = BloomFilter(n_items=300, fp_rate=1e-3)
    dsb = DeviceShardedBloom(n_items=300, fp_rate=1e-3,
                             probe_transport=transport)
    bf.add_batch(items)
    dsb.add_batch(items)
    assert dsb.contains_batch(items).all()  # no false negatives, ever
    np.testing.assert_array_equal(dsb.contains_batch(other),
                                  bf.contains_batch(other))
    np.testing.assert_array_equal(dsb.check_and_add_batch(other),
                                  ~bf.contains_batch(other))


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_in_graph_bits_are_the_host_h_mod_m_positions(transport):
    """The host formula as a reference: the lit bits are exactly the numpy
    set of `h mod m` over the filter's own 64-bit hashes of the items."""
    items = _ragged(150, 12)
    dsb = DeviceShardedBloom(n_items=200, mesh=data_mesh(1),
                             probe_transport=transport)
    dsb.add_batch(items)
    h = dsb.sharded.hasher.hash_batch(items, backend="host")
    want = np.unique(h % np.uint64(dsb.m))
    np.testing.assert_array_equal(np.flatnonzero(np.asarray(dsb.bits)), want)


def test_transports_produce_identical_bit_state():
    items, more = _ragged(150, 12), _ragged(70, 12)
    bf = BloomFilter(n_items=200)
    bf.add_batch(items)
    bf.add_batch(more)
    for t in TRANSPORTS:
        f = DeviceShardedBloom(n_items=200, probe_transport=t)
        f.add_batch(items)
        f.check_and_add_batch(more)
        bits = np.asarray(f.bits)
        np.testing.assert_array_equal(bits[:f.m] != 0, _bloom_bits(bf), t)
        assert not bits[f.m:].any(), t


def test_routed_sentinel_rows_owned_by_no_device():
    """Staged padding rows carry the owner sentinel D: they must light NO
    bits through the routed exchange (an all-invalid add leaves the filter
    empty and raises no overflow) and read back as 'present' in the raw
    verdict vector (sliced off by the host wrapper)."""
    dsb = DeviceShardedBloom(n_items=128, fp_rate=1e-2,
                             probe_transport="routed")
    toks, lens, valid, B, _ = dsb._stage(_ragged(5, 9))
    assert B == 5 and lens.shape[0] > B  # bucketing did pad
    none_valid = np.zeros_like(np.asarray(valid))
    bits, flag = dsb._add_rt(dsb.bits, dsb.sharded.hasher, toks, lens,
                             none_valid)
    assert not np.asarray(bits).any()
    assert not np.asarray(flag).any()
    verdict, _ = dsb._contains_rt(dsb.bits, dsb.sharded.hasher, toks, lens,
                                  np.asarray(valid))
    assert np.asarray(verdict)[B:].all()  # sentinel rows: zero misses


# ---------------------------------------------------------------------------
# overflow chaos (D=1; the D=4 twin runs in the subprocess test below)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("op", ["add", "contains", "check_and_add"])
def test_overflow_fallback_is_bit_identical(op, tiny_capacity):
    """Every routed launch overflows its one-slot buckets; each operation's
    replay through all_gather must still give BloomFilter-identical
    verdicts and bits, and count one fallback a launch."""
    items, other = _ragged(120, 10), _ragged(50, 10)
    bf = BloomFilter(n_items=150)
    dsb = DeviceShardedBloom(n_items=150)
    bf.add_batch(items)
    dsb.add_batch(items)  # its flag settles at the next call or bits read
    if op == "contains":
        np.testing.assert_array_equal(dsb.contains_batch(other),
                                      bf.contains_batch(other))
    elif op == "check_and_add":
        np.testing.assert_array_equal(dsb.check_and_add_batch(other),
                                      ~bf.contains_batch(other))
        bf.add_batch(other)
    bits = np.asarray(dsb.bits)
    np.testing.assert_array_equal(bits[:dsb.m] != 0, _bloom_bits(bf))
    assert dsb.stats["overflow_fallbacks"] == (1 if op == "add" else 2)


# ---------------------------------------------------------------------------
# consumers build the default routed filter (D=1 in-process)
# ---------------------------------------------------------------------------

def test_service_over_device_sharded_backends():
    from repro.hash.service import AdmissionService

    items = _ragged(64, 8)
    svc = AdmissionService.over_bloom_shards(2, 1 << 12, mesh=data_mesh())
    host_svc = AdmissionService.over_bloom_shards(2, 1 << 12)
    first = svc.admit_batch(items)
    np.testing.assert_array_equal(first, host_svc.admit_batch(items))
    assert first.all()
    assert not svc.admit_batch(items).any()
    for b in svc.transport.backends:
        assert isinstance(b.filt, DeviceShardedBloom)
        assert b.filt.transport == "routed"


def test_exact_dedup_approx_mode():
    from repro.data.dedup import ExactDedup

    docs = _ragged(80, 10)
    exact = ExactDedup()
    approx = ExactDedup(mesh=data_mesh(), approx_items=4096)
    np.testing.assert_array_equal(approx.add_documents(docs),
                                  exact.add_documents(docs))
    assert not approx.add_documents(docs).any()
    assert approx._bloom.transport == "routed"


# ---------------------------------------------------------------------------
# D=4 subprocess: transport A/B + reconcile convergence + overflow chaos
# ---------------------------------------------------------------------------

def test_multi_device_transport_equivalence_and_reconcile():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    code = """
        import numpy as np
        import jax
        from repro.data.dedup import BloomFilter
        from repro.hash import (DeviceShardedBloom, FaultEvent, FaultPlan,
                                FaultyTransport, FilterShardBackend,
                                InProcessTransport, VirtualClock,
                                bloom_shard_backends, distributed)
        from repro.hash.service import AdmissionService
        from repro.parallel.sharding import data_mesh

        assert jax.device_count() == 4
        rng = np.random.Generator(np.random.Philox(key=np.uint64(0x9704)))
        def ragged(b, n):
            return [rng.integers(0, 2**32, size=rng.integers(1, n),
                                 dtype=np.uint64).astype(np.uint32)
                    for _ in range(b)]

        items, other = ragged(200, 16), ragged(200, 16)
        bf = BloomFilter(n_items=200, fp_rate=1e-3)
        bf.add_batch(items)
        ref_mask = bf.contains_batch(other)
        bf.add_batch(other)
        ref_bits = np.unpackbits(bf.bits.view(np.uint8),
                                 bitorder="little")[:bf.m].astype(bool)
        for kind in ("all_gather", "routed"):
            f = DeviceShardedBloom(n_items=200, fp_rate=1e-3,
                                   probe_transport=kind)
            assert f.n_shards == 4
            f.add_batch(items)
            assert f.contains_batch(items).all()
            np.testing.assert_array_equal(f.contains_batch(other), ref_mask)
            np.testing.assert_array_equal(f.check_and_add_batch(other),
                                          ~ref_mask)
            bits = np.asarray(f.bits)
            np.testing.assert_array_equal(bits[:f.m] != 0, ref_bits)
            assert not bits[f.m:].any()

        # overflow chaos on a REAL 4-way exchange: fallback bit-identity
        saved = distributed._CAPACITY_FACTOR, distributed._CAPACITY_SLACK
        distributed._CAPACITY_FACTOR, distributed._CAPACITY_SLACK = 0.02, 0
        f = DeviceShardedBloom(n_items=200, fp_rate=1e-3)
        f.add_batch(items)
        np.testing.assert_array_equal(f.contains_batch(other), ref_mask)
        np.testing.assert_array_equal(f.check_and_add_batch(other),
                                      ~ref_mask)
        assert f.stats["overflow_fallbacks"] >= 1, f.stats
        np.testing.assert_array_equal(np.asarray(f.bits)[:f.m] != 0,
                                      ref_bits)
        distributed._CAPACITY_FACTOR, distributed._CAPACITY_SLACK = saved

        # admission service under faults: routed and all_gather backends
        # see identical verdicts, and after reconcile_all the sharded
        # filters converge to identical bit state
        waves = [ragged(48, 12) for _ in range(3)]
        runs = {}
        for kind in ("all_gather", "routed"):
            clock = VirtualClock()
            plan = FaultPlan(11, events=[
                FaultEvent("crash", shard=1, at=0, until=2)],
                p_timeout=0.1)
            if kind == "routed":
                backends = bloom_shard_backends(2, 1 << 12, mesh=data_mesh())
            else:
                backends = [FilterShardBackend(DeviceShardedBloom(
                    n_items=1 << 11, mesh=data_mesh(),
                    probe_transport=kind)) for _ in range(2)]
            svc = AdmissionService(
                FaultyTransport(InProcessTransport(backends), plan, clock),
                clock=clock, policy="fail_open")
            verdicts = [svc.admit_batch(w) for w in waves]
            assert svc.reconcile_all()
            runs[kind] = (verdicts,
                          [np.asarray(b.filt.bits) for b in backends])
        for va, vb in zip(*[runs[k][0] for k in ("all_gather", "routed")]):
            np.testing.assert_array_equal(va, vb)
        for ba, bb in zip(*[runs[k][1] for k in ("all_gather", "routed")]):
            np.testing.assert_array_equal(ba, bb)
        print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
