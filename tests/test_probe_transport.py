"""ProbeTransport API redesign: host/all_gather/routed A/B equivalence,
the `in_graph_mod=` deprecation shim, bucket-overflow handling, and the
consumer threading (service backends, ExactDedup, reconcile convergence).

D=1 contracts run in-process (the degenerate mesh runs the SAME routed
all_to_all code path); true multi-device behaviour (D=4) runs in a
subprocess with fake host devices, per the repo's device-count contract.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom, ProbeBucketOverflow, ProbeTransport

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x9702)))

TRANSPORTS = ["host", "all_gather", "routed"]


def _ragged(b, max_n):
    return [RNG.integers(0, 2**32, size=RNG.integers(1, max_n),
                         dtype=np.uint64).astype(np.uint32) for _ in range(b)]


# ---------------------------------------------------------------------------
# the spec object
# ---------------------------------------------------------------------------

def test_probe_transport_validation():
    assert ProbeTransport.of("routed").kind == "routed"
    pt = ProbeTransport("all_gather", capacity_factor=2.0)
    assert ProbeTransport.of(pt) is pt
    with pytest.raises(ValueError, match="kind"):
        ProbeTransport("carrier_pigeon")
    with pytest.raises(ValueError, match="on_overflow"):
        ProbeTransport("routed", on_overflow="shrug")
    with pytest.raises(ValueError, match="capacity_factor"):
        ProbeTransport("routed", capacity_factor=0.0)
    with pytest.raises(ValueError, match="capacity_slack"):
        ProbeTransport("routed", capacity_slack=-1)
    with pytest.raises(TypeError):
        ProbeTransport.of(7)


def test_probe_transport_capacity():
    pt = ProbeTransport()
    # never exceeds the probe count, never below 1
    assert pt.capacity(100, 1) == 100
    assert ProbeTransport("routed", capacity_factor=1e-9,
                          capacity_slack=0).capacity(100, 4) == 1
    # headroom: cap * D covers the probes with the factor to spare
    cap = pt.capacity(4096, 4)
    assert 4096 * 1.25 / 4 <= cap <= 4096
    # default factor >= 1 makes D=1 structurally overflow-free
    assert pt.capacity(7, 1) == 7


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


def test_transports_produce_identical_bit_state():
    items, more = _ragged(150, 12), _ragged(70, 12)
    filters = {t: DeviceShardedBloom(n_items=200, probe_transport=t)
               for t in TRANSPORTS}
    for f in filters.values():
        f.add_batch(items)
        f.check_and_add_batch(more)
    ref = np.asarray(filters["host"].bits)
    for t in ("all_gather", "routed"):
        np.testing.assert_array_equal(np.asarray(filters[t].bits), ref, t)


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
# the in_graph_mod= deprecation shim
# ---------------------------------------------------------------------------

def _one_warning(fn):
    """Run fn capturing warnings; assert exactly one DeprecationWarning."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = fn()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and "repro.hash" in str(w.message)]
    assert len(dep) == 1, [str(w.message) for w in rec]
    return out


@pytest.mark.parametrize("legacy,kind", [(True, "all_gather"),
                                         (False, "host")])
def test_in_graph_mod_shim_bit_identity(legacy, kind):
    """One DeprecationWarning, and the shim maps onto exactly the transport
    the boolean used to select -- pinned by identical bits and verdicts."""
    items, other = _ragged(100, 10), _ragged(40, 10)
    old = _one_warning(lambda: DeviceShardedBloom(
        n_items=100, in_graph_mod=legacy))
    assert old.transport.kind == kind
    assert old.in_graph_mod is legacy  # read-only property keeps answering
    new = DeviceShardedBloom(n_items=100, probe_transport=kind)
    old.add_batch(items)
    new.add_batch(items)
    np.testing.assert_array_equal(np.asarray(old.bits), np.asarray(new.bits))
    np.testing.assert_array_equal(old.check_and_add_batch(other),
                                  new.check_and_add_batch(other))


def test_probe_transport_kwarg_warns_nothing():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        DeviceShardedBloom(n_items=64, probe_transport="routed")
        DeviceShardedBloom(n_items=64,
                           probe_transport=ProbeTransport("all_gather"))
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]


# ---------------------------------------------------------------------------
# overflow chaos (D=1; the D=4 twin runs in the subprocess test below)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_overflow_fallback_is_bit_identical():
    """A pathologically tiny capacity forces bucket overflow on every call;
    the fallback path must still produce BloomFilter-identical verdicts and
    bits, and count its fallbacks."""
    items, other = _ragged(120, 10), _ragged(50, 10)
    bf = BloomFilter(n_items=150)
    tiny = ProbeTransport("routed", capacity_factor=1e-9, capacity_slack=0)
    dsb = DeviceShardedBloom(n_items=150, probe_transport=tiny)
    bf.add_batch(items)
    dsb.add_batch(items)
    np.testing.assert_array_equal(dsb.contains_batch(other),
                                  bf.contains_batch(other))
    np.testing.assert_array_equal(dsb.check_and_add_batch(other),
                                  ~bf.contains_batch(other))
    assert dsb.stats["overflow_fallbacks"] >= 2


@pytest.mark.chaos
def test_overflow_error_policy_raises_typed_error():
    tiny = ProbeTransport("routed", capacity_factor=1e-9, capacity_slack=0,
                          on_overflow="error")
    dsb = DeviceShardedBloom(n_items=150, probe_transport=tiny)
    items = _ragged(60, 10)
    with pytest.raises(ProbeBucketOverflow, match="capacity"):
        dsb.add_batch(items)   # deferred flag settles inside the batch loop
        dsb.contains_batch(items)
    # the repair ran before the raise: state is still BloomFilter-identical
    bf = BloomFilter(n_items=150)
    bf.add_batch(items)
    probe = _ragged(30, 10)
    relaxed = DeviceShardedBloom(n_items=150, probe_transport="routed")
    relaxed._bits = dsb._bits
    np.testing.assert_array_equal(relaxed.contains_batch(probe),
                                  bf.contains_batch(probe))


# ---------------------------------------------------------------------------
# consumer threading (D=1 in-process)
# ---------------------------------------------------------------------------

def test_service_over_device_sharded_backends():
    from repro.hash.service import AdmissionService
    from repro.parallel.sharding import data_mesh

    items = _ragged(64, 8)
    svc = AdmissionService.over_bloom_shards(
        2, 1 << 12, mesh=data_mesh(), probe_transport="routed")
    host_svc = AdmissionService.over_bloom_shards(2, 1 << 12)
    first = svc.admit_batch(items)
    np.testing.assert_array_equal(first, host_svc.admit_batch(items))
    assert first.all()
    assert not svc.admit_batch(items).any()
    for b in svc.transport.backends:
        assert isinstance(b.filt, DeviceShardedBloom)
        assert b.filt.transport.kind == "routed"


def test_exact_dedup_approx_mode():
    from repro.data.dedup import ExactDedup
    from repro.parallel.sharding import data_mesh

    docs = _ragged(80, 10)
    exact = ExactDedup()
    approx = ExactDedup(mesh=data_mesh(), approx_items=4096,
                        probe_transport="routed")
    np.testing.assert_array_equal(approx.add_documents(docs),
                                  exact.add_documents(docs))
    assert not approx.add_documents(docs).any()
    assert approx._bloom.transport.kind == "routed"


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
                                FaultyTransport, InProcessTransport,
                                ProbeBucketOverflow, ProbeTransport,
                                VirtualClock, bloom_shard_backends)
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
        bits = {}
        for kind in ("host", "all_gather", "routed"):
            f = DeviceShardedBloom(n_items=200, fp_rate=1e-3,
                                   probe_transport=kind)
            assert f.n_shards == 4
            f.add_batch(items)
            assert f.contains_batch(items).all()
            np.testing.assert_array_equal(f.contains_batch(other), ref_mask)
            np.testing.assert_array_equal(f.check_and_add_batch(other),
                                          ~ref_mask)
            bits[kind] = np.asarray(f.bits)
        np.testing.assert_array_equal(bits["host"], bits["all_gather"])
        np.testing.assert_array_equal(bits["host"], bits["routed"])

        # overflow chaos on a REAL 4-way exchange: fallback bit-identity
        tiny = ProbeTransport("routed", capacity_factor=0.02,
                              capacity_slack=0)
        f = DeviceShardedBloom(n_items=200, fp_rate=1e-3,
                               probe_transport=tiny)
        f.add_batch(items)
        np.testing.assert_array_equal(f.contains_batch(other), ref_mask)
        np.testing.assert_array_equal(f.check_and_add_batch(other),
                                      ~ref_mask)
        assert f.stats["overflow_fallbacks"] >= 1, f.stats
        np.testing.assert_array_equal(np.asarray(f.bits), bits["host"])
        hard = ProbeTransport("routed", capacity_factor=0.02,
                              capacity_slack=0, on_overflow="error")
        f = DeviceShardedBloom(n_items=200, fp_rate=1e-3,
                               probe_transport=hard)
        try:
            f.add_batch(items); f.contains_batch(items)
            raise SystemExit("expected ProbeBucketOverflow")
        except ProbeBucketOverflow:
            pass

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
            backends = bloom_shard_backends(
                2, 1 << 12, mesh=data_mesh(),
                probe_transport=kind)
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
