"""Tree-fingerprint bench (repro.hash.tree): leaf-launch throughput, fold
tail cost, end-to-end digest rate, and the serial `stream_digest_host`
baseline the tree path replaces for long inputs.

Row families:
  tree/leaf_hash/<T>   -- the fused all-leaves multihash launch alone
                          (BLOCKING gate: this is the new hot path)
  tree/digest/<T>      -- jitted leaf+fold+finalize digest_tokens
                          (BLOCKING gate)
  tree/fold_host/L<n>  -- numpy fold tail over n leaf digests (report-only:
                          O(n_leaves) work on 8-byte nodes, noise-bound)
  tree/stream/<T>      -- TreeStream incremental absorb+digest (report-only)
  tree/stream_host/<T> -- the pre-tree serial two-level host loop on the
                          same input (report-only baseline; the D-scaling
                          rows come from the `distributed` module)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.hash import Hasher, HashSpec, stream_digest_host
from repro.hash.tree import TreeHasher, TreeSpec

from . import common
from .common import row, timeit


def run() -> None:
    fast = common.FAST
    T = 1 << 14 if fast else 1 << 20  # tokens (64 KiB / 4 MiB)
    lw = 256
    reps_gated = 1 if fast else 7
    reps = 1 if fast else 3
    n_bytes = T * 4

    rng = np.random.Generator(np.random.Philox(key=np.uint64(0x73EE)))
    toks = rng.integers(0, 2**32, size=T, dtype=np.uint64).astype(np.uint32)
    th = TreeHasher(TreeSpec(leaf_words=lw))

    # leaf pass alone: one fused engine launch over all T/lw leaves
    rows = jnp.asarray(toks.reshape(T // lw, lw))
    leaf_fn = jax.jit(lambda r: th.hasher(r))
    t, s = timeit(leaf_fn, rows, repeats=reps_gated, inner=1, warmup=2,
                  return_samples=True)
    row(f"tree/leaf_hash/{T}", t * 1e6,
        f"{T // lw} leaves x {lw} words, one fused launch",
        n_bytes=n_bytes, samples_us=s)

    # full digest: leaf pass + log2(T/lw) fold levels + finalization
    dtoks = jnp.asarray(toks)
    dig_fn = jax.jit(lambda tk: th.digest_tokens(tk))
    t_dig, s = timeit(dig_fn, dtoks, repeats=reps_gated, inner=1, warmup=2,
                      return_samples=True)
    row(f"tree/digest/{T}", t_dig * 1e6,
        f"leaf+fold+finalize; fold tail adds x{t_dig / t:.2f} of leaf pass",
        n_bytes=n_bytes, samples_us=s)

    # fold tail in isolation (host twin arithmetic: same mod-2^64 values)
    n_leaves = T // lw
    digs = rng.integers(0, 2**64, size=n_leaves, dtype=np.uint64)
    t_fold = timeit(lambda: th._fold_host(digs, T), repeats=reps, inner=1,
                    warmup=1)
    row(f"tree/fold_host/L{n_leaves}", t_fold * 1e6,
        "numpy pairwise fold over leaf digests (8 B/leaf)",
        n_bytes=n_leaves * 8)

    # incremental stream (device leaf flushes, host fold)
    def stream_once():
        s = th.stream(leaf_batch=1024)
        step = 1 << 12 if fast else 1 << 16
        for i in range(0, T, step):
            s.update(toks[i : i + step])
        return s.digest_int()

    t_stream = timeit(stream_once, repeats=reps, inner=1, warmup=1)
    row(f"tree/stream/{T}", t_stream * 1e6,
        "TreeStream absorb+digest, batched leaf flushes", n_bytes=n_bytes)

    # the serial pre-tree baseline on the same input: a python host loop
    # over chunks (this is what long inputs used to cost)
    h = Hasher.from_spec(HashSpec(family="multilinear", n_hashes=1,
                                  out_bits=64, seed=0x73EE), max_len=lw)
    t_host = timeit(lambda: stream_digest_host(h, toks, lw,
                                               max_chunks=T // lw + 1),
                    repeats=reps, inner=1, warmup=1)
    row(f"tree/stream_host/{T}", t_host * 1e6,
        f"serial two-level host loop; tree digest is x{t_host / t_dig:.1f} "
        "faster single-device", n_bytes=n_bytes)
