"""Distributed scale-out bench: ShardedHasher / DeviceShardedBloom vs the
single-device engine, as the `distributed` module of `benchmarks.run`.

`run()` benches on the LIVE device set in the calling process -- on a
one-device CPU runner this measures the shard_map degrade overhead (mesh of
size 1, same code path), which must stay small. There is no fake-device mode:
a CPU process with forced host devices measures CPU collectives, and its
rows would pass for device numbers.
"""
from __future__ import annotations

import numpy as np

from . import common
from .common import row, timeit


def _items(B: int, L: int) -> np.ndarray:
    rng = np.random.Generator(np.random.Philox(key=np.uint64(0xD157)))
    return rng.integers(0, 2**32, size=(B, L), dtype=np.uint64).astype(np.uint32)


def _bench_meshes(meshes: "list[tuple[str, object]]") -> None:
    """Per-mesh rows for the sharded hash engine + sharded Bloom admission.

    meshes: (tag, mesh-or-None) pairs; None = plain single-device Hasher /
    BloomFilter reference rows.
    """
    from repro.data.dedup import BloomFilter
    from repro.hash import DeviceShardedBloom, Hasher, HashSpec

    fast = common.FAST
    B = 512 if fast else 4096
    L, K = 16, 4
    toks = _items(B, L)
    n_bytes = B * L * 4
    reps = 1 if fast else 3

    spec = HashSpec(family="multilinear", n_hashes=K, seed=0xD157)
    for tag, mesh in meshes:
        if mesh is None:
            hasher = Hasher.from_spec(spec, max_len=L)
            fn = lambda: hasher.hash_batch(toks, backend="jnp")  # noqa: E731
        else:
            sharded = Hasher.from_spec(spec, max_len=L).sharded(mesh)
            fn = lambda: sharded.hash_batch(toks)  # noqa: E731
        t = timeit(fn, repeats=reps, inner=1, warmup=1)
        row(f"distributed/hash_batch/B{B}xK{K}/{tag}", t * 1e6,
            "single-device engine" if mesh is None else
            f"shard_map over {tag}", n_bytes=n_bytes)

    for tag, mesh in meshes:
        if mesh is None:
            bf = BloomFilter(n_items=B, fp_rate=1e-3)

            def fn(bf=bf):
                bf.add_batch(toks)
                return bf.contains_batch(toks)
        else:
            dsb = DeviceShardedBloom(n_items=B, fp_rate=1e-3, mesh=mesh)

            def fn(dsb=dsb):
                dsb.add_batch(toks)
                return dsb.contains_batch(toks)
        t = timeit(fn, repeats=reps, inner=1, warmup=1)
        row(f"distributed/bloom{B}/add+contains/{tag}", t * 1e6,
            "host packed-word filter" if mesh is None else
            f"range-partitioned bits, one psum ({tag})", n_bytes=n_bytes)

    # fused admission, one row per probe transport: 'ingraph' the
    # limbs.mod_u64 Barrett reduction + probe all_gather inside the launch,
    # 'routed' the owner-bucketed all_to_all exchange (the default
    # transport; its rows sit under the blocking regression gate, hence
    # samples_us at a gate-grade repeat count -- 3 baseline + 6 fresh
    # repeats cannot clear the permutation test's alpha=0.01)
    derived = {"ingraph": "in-graph Barrett mod + probe all_gather",
               "routed": "owner-bucketed probe all_to_all"}
    transports = {"ingraph": "all_gather", "routed": "routed"}
    admit_reps = 3 if fast else 7
    for tag, mesh in meshes:
        if mesh is None:
            continue
        for mode in ("ingraph", "routed"):
            dsb = DeviceShardedBloom(n_items=B, fp_rate=1e-3, mesh=mesh,
                                     probe_transport=transports[mode])
            fn = lambda dsb=dsb: dsb.check_and_add_batch(toks)  # noqa: E731
            t, samples = timeit(fn, repeats=admit_reps, inner=1, warmup=1,
                                return_samples=True)
            row(f"distributed/bloom_admit/B{B}/{mode}/{tag}", t * 1e6,
                derived[mode], n_bytes=n_bytes, samples_us=samples)


def _bench_tree(meshes: "list[tuple[str, object]]") -> None:
    """Tree-fingerprint D-scaling rows: the fused leaf pass sharded over the
    'data' axis vs single-device, plus the serial `stream_digest_host` loop
    the tree path replaces -- the long-input speedup claim lives here
    (acceptance: sharded leaf hashing >= 2x the serial host baseline)."""
    from repro.hash import Hasher, HashSpec, stream_digest_host
    from repro.hash.tree import TreeHasher, TreeSpec

    fast = common.FAST
    T = 1 << 14 if fast else 1 << 18  # tokens; 1024 leaves at full size
    lw = 256
    reps = 1 if fast else 3
    n_bytes = T * 4
    rng = np.random.Generator(np.random.Philox(key=np.uint64(0x73EE)))
    toks = rng.integers(0, 2**32, size=T, dtype=np.uint64).astype(np.uint32)

    want = None
    for tag, mesh in meshes:
        th = TreeHasher(TreeSpec(leaf_words=lw), mesh=mesh)
        fp = th.fingerprint(toks)
        want = fp if want is None else want
        assert fp == want, f"digest drift on {tag}: {fp:#x} != {want:#x}"
        t = timeit(th.fingerprint, toks, repeats=reps, inner=1, warmup=1)
        row(f"distributed/tree_digest/T{T}/{tag}", t * 1e6,
            "single-device leaf pass" if mesh is None else
            f"leaves sharded over {tag}, host fold tail", n_bytes=n_bytes)

    # the pre-tree serial route for the same input: a python host loop
    h = Hasher.from_spec(HashSpec(family="multilinear", n_hashes=1,
                                  out_bits=64, seed=0x73EE), max_len=lw)
    t = timeit(lambda: stream_digest_host(h, toks, lw,
                                          max_chunks=T // lw + 1),
               repeats=reps, inner=1, warmup=1)
    row(f"distributed/tree_digest/T{T}/stream_host_baseline", t * 1e6,
        "serial two-level host loop (the route tree replaces)",
        n_bytes=n_bytes)


def _bench_service() -> None:
    """p50/p99 admission latency through the fault-tolerant service
    (repro.hash.service), healthy vs under a seeded fault plan. Report-only
    rows (never gated: tail latency on a shared runner is noise-bound). The
    virtual clock means injected timeouts/backoffs cost ZERO wall time, so
    the 'faulty' rows isolate the service's retry/breaker/journal
    control-flow overhead -- the part this repo owns."""
    import time as _time

    from repro.hash import (AdmissionService, FaultEvent, FaultPlan,
                            FaultyTransport, InProcessTransport,
                            VirtualClock, bloom_shard_backends)

    fast = common.FAST
    n_batches = 16 if fast else 64
    B = 64
    rng = np.random.Generator(np.random.Philox(key=np.uint64(0xAD41)))
    batches = [[rng.integers(0, 5000, int(rng.integers(4, 16)),
                             dtype=np.uint32).astype(np.uint32)
                for _ in range(B)] for _ in range(n_batches)]
    n_bytes = int(sum(len(r) for b in batches for r in b) * 4 / n_batches)
    # warm every pow2 hash-launch bucket ONCE up front: the in-process jit
    # cache is shared across modes, so without this the first mode timed
    # would pay all the compiles and its p99 would measure XLA, not the
    # service
    warm = AdmissionService(
        InProcessTransport(bloom_shard_backends(4, 1 << 16)),
        clock=VirtualClock())
    for batch in batches:
        warm.admit_batch(batch)
    for mode in ("healthy", "faulty"):
        backends = bloom_shard_backends(4, 1 << 16)
        clock = VirtualClock()
        transport = InProcessTransport(backends)
        if mode == "faulty":
            plan = FaultPlan(29, events=[FaultEvent("crash", shard=1,
                                                    at=3, until=9)],
                             p_timeout=0.02, p_drop=0.02, p_corrupt=0.02)
            transport = FaultyTransport(transport, plan, clock)
        svc = AdmissionService(transport, clock=clock, policy="fail_open")
        svc.admit_batch(batches[0])  # warmup: jit the hash launches
        lat = []
        for batch in batches:
            t0 = _time.perf_counter()
            svc.admit_batch(batch)
            lat.append(_time.perf_counter() - t0)
        note = ("L1/L2 service, no faults" if mode == "healthy" else
                "crash window + 6% random faults (retry/breaker path)")
        for q in (50, 99):
            row(f"distributed/service_admit/B{B}/{mode}/p{q}",
                float(np.percentile(lat, q)) * 1e6, note, n_bytes=n_bytes)


def run() -> None:
    """benchmarks.run module hook: live device set (D=1 on the CI runner)."""
    from repro.parallel.sharding import data_mesh

    mesh = data_mesh()
    d = mesh.devices.size
    _bench_meshes([("single", None), (f"D{d}", mesh)])
    _bench_tree([("single", None), (f"D{d}", mesh)])
    _bench_service()
