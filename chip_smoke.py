#!/usr/bin/env python3
"""Smoke run of the hashing engine and its consumers on a TPU.

    python chip_smoke.py             # one chip: engine, admission, tree, serve
    python chip_smoke.py --chips 4   # the four-chip mesh path, and only that

Everything runs in this one process (a chip belongs to one process; this
script starts no other). Each phase prints one line with its sizes and its
wall time -- a smoke timing, not a benchmark -- and checks its results bit
for bit against the repo's host references. The last line of standard output
is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and it is printed only when every phase passed on a TPU through the Pallas
kernels. On any other platform, or outside a checkout of this repository,
the script exits non-zero without it.

Phases on one chip:
  engine     `Hasher` on every engine family at a corpus shape (4096 ragged
             rows of up to 4096 tokens, K=4) and a serve shape (a few short
             rows), plus `probe_indices` at m=4097 and m=2^32-1, against
             `hash_batch(backend="host")`.
  admission  `DeviceShardedBloom` sized for 10^8 items at fp 10^-3 (1.44 GB
             of device bits), waves of 4096 items with planted repeats,
             against the host `BloomFilter`.
  tree       `TreeHasher` fingerprint of a 256 MiB array against the numpy
             twin, `TreeStream` over uneven chunks and the pure-JAX digest.
  serve      granite_moe_hash (Granite-3.0-1B-A400M widths, hash router,
             random bf16 weights) through `ServeEngine` with admission, then
             its parameters through `Checkpointer` save / verify / restore.
Phases with --chips 4 (mesh over 4 devices):
  mesh_engine     `ShardedHasher` against the single-device `Hasher`
                  (integer and GF), output spread over 4 devices.
  mesh_admission  routed and all_gather `DeviceShardedBloom` against the
                  host `BloomFilter`, with the bits spread over 4 devices.
  mesh_tree       the D=4 tree digest against D=1, the numpy twin, the
                  pure-JAX digest and uneven stream chunks.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, ".chip_smoke")  # checkpoint scratch, removed after


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Problem sizes of every phase (FULL on the chip; tests pass tiny ones)."""

    engine_rows: int = 4096
    engine_width: int = 4096
    engine_hashes: int = 4
    serve_rows: int = 5
    serve_width: int = 24
    bloom_items: int = 10**8
    bloom_wave: int = 4096
    bloom_waves: int = 4
    bloom_repeats: int = 256        # planted repeats per wave after the first
    tree_bytes: int = 256 << 20
    arch: str = "granite_moe_hash"
    smoke_arch: bool = False
    n_requests: int = 12            # 3 of them repeat an earlier prompt
    max_new_tokens: int = 8
    admission_items: int = 1 << 20


FULL = Sizes()


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=np.uint64(seed) ^ np.uint64(tag)))


def _u32(rng, shape) -> np.ndarray:
    return rng.integers(0, 2**32, size=shape, dtype=np.uint64).astype(np.uint32)


class SmokeFailure(RuntimeError):
    """A phase's result differs from its reference (or ran off the chip)."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _report(phase: str, t0: float, **sizes) -> None:
    parts = " ".join(f"{k}={v}" for k, v in sizes.items())
    print(f"phase={phase} {parts} seconds={time.perf_counter() - t0:.3f}",
          flush=True)


def _limbs_u64(out) -> np.ndarray:
    """(..., 2) uint32 (hi, lo) slots -> (...) uint64."""
    out = np.asarray(out)
    return (out[..., 0].astype(np.uint64) << np.uint64(32)) | out[..., 1]


def _host_hashes(hasher, rows: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """`hash_batch(backend="host")` over row chunks on a thread pool (numpy
    releases the GIL); the first chunk runs alone so the key buffer has
    grown to full width before the threads read it."""
    step = 128
    first = hasher.hash_batch(rows[:step], lengths=lens[:step], backend="host")
    starts = range(step, len(rows), step)
    with concurrent.futures.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        rest = list(ex.map(lambda i: hasher.hash_batch(
            rows[i:i + step], lengths=lens[i:i + step], backend="host"), starts))
    return np.concatenate([first, *rest])


def _ragged(rng, n: int, width: int, lo: int = 1):
    lens = rng.integers(lo, width + 1, size=n).astype(np.int64)
    rows = _u32(rng, (n, width))
    rows[np.arange(width)[None, :] >= lens[:, None]] = 0
    return rows, lens


def _compiled_with_kernel(fn, *args, kernel: bool):
    """jit + AOT-compile `fn`; with `kernel`, require the Pallas custom call
    in the compiled program (no silent jnp or host fallback)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if kernel:
        _check("tpu_custom_call" in compiled.as_text(), "no Pallas kernel in HLO")
    return compiled


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_engine(sz: Sizes, backend: str, seed: int) -> None:
    import jax.numpy as jnp

    from repro.core.limbs import ModPlan
    from repro.hash import FAMILY_NAMES, Hasher, HashSpec

    rng = _rng(seed, 0xE5)
    B, N, K = sz.engine_rows, sz.engine_width, sz.engine_hashes
    rows, lens = _ragged(rng, B, N)
    srows, slens = _ragged(rng, sz.serve_rows, sz.serve_width)
    kernel = backend == "pallas"
    call = lambda hs, t, n: hs(t, n)  # noqa: E731
    for fam in FAMILY_NAMES:
        t0 = time.perf_counter()
        spec = HashSpec(family=fam, n_hashes=K, out_bits=64,
                        variable_length=True, seed=seed ^ 0x5EED)
        h = Hasher.from_spec(spec, max_len=N)
        _check(h.plan.backend == backend, f"{fam}: {h.plan}")
        for toks, ls in ((rows, lens), (srows, slens)):
            t, n = jnp.asarray(toks), jnp.asarray(ls)
            got = _limbs_u64(_compiled_with_kernel(call, h, t, n,
                                                   kernel=kernel)(h, t, n))
            want = _host_hashes(h, toks, ls)
            _check(np.array_equal(got, want), f"{fam}: hash mismatch")
            for m in (4097, 2**32 - 1):
                plan = ModPlan.for_modulus(m)
                probe = lambda hs, t, n, plan=plan: hs.probe_indices(t, plan, n)  # noqa: E731
                idx = np.asarray(_compiled_with_kernel(
                    probe, h, t, n, kernel=kernel)(h, t, n))
                _check(np.array_equal(idx, (want % np.uint64(m)).astype(np.uint32)),
                       f"{fam}: probe mismatch at m={m}")
        _report("engine", t0, family=fam, B=B, N=N, K=K,
                serve_B=sz.serve_rows, serve_N=sz.serve_width, backend=backend)


def _bloom_waves(sz: Sizes, seed: int):
    """Waves of fresh short rows; every wave after the first also carries
    `bloom_repeats` rows seen in earlier waves. Yields (items, n_planted)."""
    rng = _rng(seed, 0xB1)
    seen: list = []
    for w in range(sz.bloom_waves):
        planted = sz.bloom_repeats if w else 0
        fresh_rows, fresh_lens = _ragged(rng, sz.bloom_wave - planted, 32, lo=4)
        fresh = [r[:n] for r, n in zip(fresh_rows, fresh_lens)]
        reps = [seen[i] for i in rng.choice(len(seen), planted, replace=False)] \
            if planted else []
        items = fresh + reps
        order = rng.permutation(len(items))
        seen.extend(fresh)
        yield [items[i] for i in order], planted


def _check_admission(sz: Sizes, seed: int, blooms: dict, phase: str,
                     t0: float, **extra) -> None:
    from repro.data.dedup import BloomFilter

    host = BloomFilter(n_items=sz.bloom_items, fp_rate=1e-3, backend="host")
    rejects = dict.fromkeys(blooms, 0)
    planted = 0
    for items, n_planted in _bloom_waves(sz, seed):
        want = host.check_and_add_batch(items)
        planted += n_planted
        for name, dsb in blooms.items():
            got = dsb.check_and_add_batch(items)
            _check(np.array_equal(got, want), f"{name}: verdicts differ from host")
            rejects[name] += int((~got).sum())
    _check(all(r == planted for r in rejects.values()),
           f"rejects {rejects} != planted {planted}")
    dsb = next(iter(blooms.values()))
    _report(phase, t0, items=sz.bloom_items, m_bits=dsb.m, k=dsb.k,
            device_bytes=int(dsb.bits.size), waves=sz.bloom_waves,
            wave=sz.bloom_wave, planted=planted, **extra)


def phase_admission(sz: Sizes, backend: str, seed: int) -> None:
    from repro.hash import DeviceShardedBloom

    t0 = time.perf_counter()
    dsb = DeviceShardedBloom(n_items=sz.bloom_items, fp_rate=1e-3)
    _check(dsb.sharded.hasher.plan.backend == backend, "bloom off the kernel")
    _check_admission(sz, seed, {"routed": dsb}, "admission", t0,
                     transport="routed", backend=backend)


def _tree_checks(th, toks: np.ndarray, kernel: bool) -> int:
    """Fingerprint `toks` four ways (host staging, pure-JAX digest, uneven
    stream chunks, numpy twin); all must agree. Returns the digest."""
    import jax.numpy as jnp

    fp = th.fingerprint(toks)
    host = th.digest_host(toks)
    dev = _compiled_with_kernel(th.digest_tokens, jnp.asarray(toks),
                                kernel=kernel)(jnp.asarray(toks))
    pure = (int(dev[0]) << 32) | int(dev[1])
    n = len(toks)
    cuts = sorted({0, 1, 256, 257, 3 * 4096 + 5, n // 3, n // 3 + 12345, n})
    stream = th.stream(leaf_batch=1024)
    for a, b in zip(cuts, cuts[1:]):
        stream.update(toks[min(a, n):min(b, n)])
    streamed = stream.digest_int()
    _check(fp == host == pure == streamed,
           f"tree digests differ: {fp:#x} {host:#x} {pure:#x} {streamed:#x}")
    return fp


def phase_tree(sz: Sizes, backend: str, seed: int) -> None:
    from repro.hash import TreeHasher, TreeSpec

    t0 = time.perf_counter()
    toks = _u32(_rng(seed, 0x7E), sz.tree_bytes // 4)
    th = TreeHasher(TreeSpec(seed=seed ^ 0x7EE))
    _check(th.hasher.plan.backend == backend, "tree off the kernel")
    fp = _tree_checks(th, toks, kernel=backend == "pallas")
    _report("tree", t0, bytes=sz.tree_bytes, leaf_words=th.spec.leaf_words,
            digest=f"{fp:016x}", backend=backend)


def _requests(sz: Sizes, vocab: int, seed: int):
    """`n_requests` prompts; requests 5, 8, 11, ... repeat the prompt five
    positions earlier, so a repeat never shares a four-slot admission wave
    with its original. Returns (requests, n_repeats)."""
    from repro.serve import Request

    rng = _rng(seed, 0x5E)
    lengths = (16, 24, 32)
    prompts: list = []
    n_rep = 0
    for i in range(sz.n_requests):
        if i >= 5 and i % 3 == 2:
            prompts.append(prompts[i - 5].copy())
            n_rep += 1
        else:
            prompts.append(rng.integers(0, vocab, size=lengths[i % 3]).astype(np.int32))
    return [Request(i, p, max_new_tokens=sz.max_new_tokens)
            for i, p in enumerate(prompts)], n_rep


def phase_serve(sz: Sizes, backend: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import Checkpointer
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import ServeEngine

    t0 = time.perf_counter()
    cfg = get_config(sz.arch, smoke=sz.smoke_arch)
    api = build(cfg)

    def init(key):
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
            api.init(key))

    params = jax.jit(init)(jax.random.key(seed))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    eng = ServeEngine(api, params, n_slots=4, max_seq=64,
                      admission_items=sz.admission_items)
    l2 = eng.admission.transport.backends[0].filt
    _check(eng._prefix_hasher.plan.backend == backend
           and l2.sharded.hasher.plan.backend == backend, "serve off the kernel")
    reqs, n_rep = _requests(sz, cfg.vocab_size, seed)
    eng.submit_all(reqs)
    st = eng.stats
    _check(st["admission_errors"] == 0, f"admission errors: {st}")
    _check(st["admission_rejects"] == n_rep, f"{n_rep} repeats planted: {st}")
    admitted = [r for r in reqs if r.admitted]
    _check(len(admitted) == len(reqs) - n_rep and all(r.done for r in reqs)
           and all(len(r.out_tokens) == sz.max_new_tokens for r in admitted)
           and all(0 <= t < cfg.vocab_size
                   for r in admitted for t in r.out_tokens),
           "a request did not complete with max_new_tokens valid tokens")
    # prompt keys: the sharded device launch against the host path
    eng._precompute_prompt_keys(reqs)
    eng._drain_prompt_keys()
    for r in reqs:
        _check(eng._req_key_cache.pop(r.req_id) == eng._prompt_key(r.prompt),
               f"request {r.req_id}: device prompt key != host key")
    _report("serve", t0, arch=cfg.name, params=n_params, requests=len(reqs),
            repeats=n_rep, max_new_tokens=sz.max_new_tokens,
            prefills=st["prefills"], ticks=st["ticks"], backend=backend)

    t0 = time.perf_counter()
    ckdir = os.path.join(WORKDIR, "ckpt")
    shutil.rmtree(ckdir, ignore_errors=True)
    try:
        ck = Checkpointer(ckdir, keep=1)
        ck.save(1, params)
        _check(ck.verify(1) and ck.latest_valid() == 1, "checkpoint not valid")
        restored = ck.restore(1, params)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            _check(a.dtype == b.dtype and a.shape == b.shape, "restore layout")
            _check(np.array_equal(np.asarray(a).view(np.uint8),
                                  np.asarray(b).view(np.uint8)), "restore differs")
        nbytes = sum(os.path.getsize(os.path.join(ckdir, "step_1", f))
                     for f in os.listdir(os.path.join(ckdir, "step_1")))
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    _report("checkpoint", t0, scheme="tree-v1", params=n_params,
            leaves=len(jax.tree.leaves(params)), disk_bytes=nbytes,
            backend=backend)


ONE_CHIP = (phase_engine, phase_admission, phase_tree, phase_serve)


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def _spread(arr, n_dev: int) -> None:
    """`arr` must be split over `n_dev` distinct devices."""
    devs = {s.device for s in arr.addressable_shards}
    _check(len(devs) == n_dev, f"array on {len(devs)} devices, want {n_dev}")


def phase_mesh_engine(sz: Sizes, backend: str, seed: int, mesh) -> None:
    import jax.numpy as jnp

    from repro.hash import Hasher, HashSpec

    D = mesh.devices.size
    rng = _rng(seed, 0x4E)
    B, N, K = sz.engine_rows, sz.engine_width, sz.engine_hashes
    rows, lens = _ragged(rng, B, N)
    t, n = jnp.asarray(rows), jnp.asarray(lens)
    for fam in ("multilinear", "gf_multilinear"):
        t0 = time.perf_counter()
        h = Hasher.from_spec(HashSpec(family=fam, n_hashes=K, out_bits=64,
                                      variable_length=True, seed=seed),
                             max_len=N)
        _check(h.plan.backend == backend, f"{fam}: {h.plan}")
        got = h.sharded(mesh)(t, n)
        _spread(got, D)
        _check(np.array_equal(np.asarray(got), np.asarray(h(t, n))),
               f"{fam}: D={D} != D=1")
        _report("mesh_engine", t0, family=fam, devices=D, B=B, N=N, K=K,
                backend=backend)


def phase_mesh_admission(sz: Sizes, backend: str, seed: int, mesh) -> None:
    from repro.hash import DeviceShardedBloom

    t0 = time.perf_counter()
    D = mesh.devices.size
    blooms = {kind: DeviceShardedBloom(n_items=sz.bloom_items, fp_rate=1e-3,
                                       mesh=mesh, probe_transport=kind)
              for kind in ("routed", "all_gather")}
    for dsb in blooms.values():
        _check(dsb.n_shards == D and dsb.sharded.hasher.plan.backend == backend,
               "bloom not on the mesh kernel")
        _spread(dsb.bits, D)
    _check_admission(sz, seed, blooms, "mesh_admission", t0, devices=D,
                     transports="routed,all_gather", backend=backend)
    a, b = (np.asarray(d.bits) for d in blooms.values())
    _check(np.array_equal(a, b), "routed and all_gather bits differ")


def phase_mesh_tree(sz: Sizes, backend: str, seed: int, mesh) -> None:
    from repro.hash import TreeHasher, TreeSpec

    t0 = time.perf_counter()
    toks = _u32(_rng(seed, 0x7E), sz.tree_bytes // 4)
    spec = TreeSpec(seed=seed ^ 0x7EE)
    one = TreeHasher(spec).fingerprint(toks)
    th = TreeHasher(spec, mesh=mesh)
    _check(th.sharded.n_shards == mesh.devices.size, "tree not on the mesh")
    many = _tree_checks(th, toks, kernel=backend == "pallas")
    _check(one == many, f"D={mesh.devices.size} digest {many:#x} != D=1 {one:#x}")
    _report("mesh_tree", t0, devices=mesh.devices.size, bytes=sz.tree_bytes,
            digest=f"{many:016x}", backend=backend)


MESH = (phase_mesh_engine, phase_mesh_admission, phase_mesh_tree)


# ---------------------------------------------------------------------------

def _entries(cache: str) -> int:
    return len(os.listdir(cache)) if os.path.isdir(cache) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: the mesh path only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random data and weights")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache} "
          f"entries={_entries(cache)}", flush=True)
    if args.chips == 1:
        for phase in ONE_CHIP:
            phase(FULL, "pallas", args.seed)
    else:
        from repro.parallel.sharding import data_mesh

        mesh = data_mesh(args.chips)
        for phase in MESH:
            phase(FULL, "pallas", args.seed, mesh)
    print(f"compile_cache entries={_entries(cache)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
