"""`Hasher` -- a jit-native, pytree-registered hashing engine.

A `Hasher` binds a `HashSpec` (scheme) to explicit key planes (material) and
an execution `HashPlan` (backend/block shapes). Three call surfaces:

- ``hasher(tokens, lengths=None)`` -- PURE JAX: device arrays in, device
  arrays out, zero host syncs. Composes under `jit`, `vmap`, `shard_map`;
  the key planes are ordinary pytree leaves, so a Hasher can be a jitted
  function argument, live inside a train-state pytree, or be donated.
- ``hasher.hash_batch(items, ...)`` -- the host-convenience batched engine
  (numpy/ragged in, numpy out, one fused launch per batch). This is the
  bit-identical successor of the legacy ``core.ops.hash_tokens_device_multi``
  free function and what the host-side consumers (Bloom, dedup, pipeline,
  serve) drive.
- ``hasher.stream()/.update()/.digest()`` -- incremental two-level UMAC-style
  fingerprint tree over device token streams (streaming.py).

Pytree layout: children = (key_hi, key_lo) uint32 (K, cap+1) planes with m1
at column 0; static aux = (spec, plan, key buffer). Equal (spec, plan) +
same buffer object => shared jit cache entries.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core import hostref, limbs
from ..core.keys import MultiKeyBuffer
from .spec import HashSpec

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class HashPlan:
    """Execution half: which compute path `__call__` lowers to.

    backend: 'jnp' (fused XLA -- default off-TPU, vmap-safe), 'pallas'
      (TPU kernel), 'interpret' (kernel body in Python on CPU).
    block_b/block_n: kernel tile shape (pallas/interpret only).
    """

    backend: str = "jnp"
    block_b: int = 8
    block_n: int = 1024

    def __post_init__(self):
        if self.backend not in ("jnp", "pallas", "interpret"):
            raise ValueError(f"unknown backend {self.backend!r}")


def default_plan() -> HashPlan:
    return HashPlan(backend="pallas" if jax.default_backend() == "tpu" else "jnp")


def _even(n: int) -> int:
    return n + (n & 1)


def _stack_ragged(tokens):
    """Normalize tokens to (B, N) uint32 + per-row lengths (or None if the
    input was already a dense 2-D batch)."""
    if isinstance(tokens, (list, tuple)):
        rows = [np.atleast_1d(np.asarray(r)).astype(np.uint32) for r in tokens]
        n = max((len(r) for r in rows), default=0)
        out = np.zeros((len(rows), n), np.uint32)
        for i, r in enumerate(rows):
            out[i, : len(r)] = r
        return out, np.asarray([len(r) for r in rows], np.int64)
    arr = np.atleast_2d(np.asarray(tokens)).astype(np.uint32)
    return arr, None


class Hasher:
    """K strongly universal hash functions as one immutable, jit-native object.

    Construct with `Hasher.from_spec(spec)` (keys derived from the spec's
    seeds) or `Hasher.from_keys(mkb, spec)` (bind an existing key buffer).
    All methods are functional: capacity growth returns a NEW Hasher
    (`ensure`), the underlying Philox streams guarantee the widened planes
    extend the old ones bit-exactly.
    """

    def __init__(self, key_hi, key_lo, spec: HashSpec,
                 plan: HashPlan | None = None,
                 _mkb: MultiKeyBuffer | None = None):
        self._key_hi = key_hi
        self._key_lo = key_lo
        self.spec = spec
        self.plan = plan or default_plan()
        self._mkb = _mkb

    # Key planes are materialized on device lazily: host-only consumers
    # (hash_batch reads keys straight from the key buffer) never pay the
    # upload, and the deprecation shims can build a Hasher per call for
    # free. numpy planes are swapped for the jnp array on first access.
    @property
    def key_hi(self):
        self._key_hi = self._materialize(self._key_hi)
        return self._key_hi

    @property
    def key_lo(self):
        self._key_lo = self._materialize(self._key_lo)
        return self._key_lo

    @staticmethod
    def _materialize(plane):
        if not isinstance(plane, np.ndarray):
            return plane
        arr = jnp.asarray(plane)
        # first access from inside a trace yields a per-trace constant
        # tracer: use it for this trace but do NOT cache it (it would leak)
        return plane if isinstance(arr, jax.core.Tracer) else arr

    # -- construction --------------------------------------------------------

    @classmethod
    def from_spec(cls, spec: HashSpec = HashSpec(), *, max_len: int = 256,
                  plan: HashPlan | None = None) -> "Hasher":
        mkb = MultiKeyBuffer(seeds=list(spec.stream_seeds()))
        return cls.from_keys(mkb, spec, max_len=max_len, plan=plan)

    @classmethod
    def from_keys(cls, mkb: MultiKeyBuffer, spec: HashSpec, *,
                  max_len: int = 256, plan: HashPlan | None = None) -> "Hasher":
        if mkb.n_hashes != spec.n_hashes:
            raise ValueError(
                f"key buffer has {mkb.n_hashes} streams, spec wants "
                f"{spec.n_hashes}")
        from ..kernels.autotune import pow2_at_least

        cap = pow2_at_least(max(2, _even(max_len + 2)))
        hi, lo = mkb.planes(cap + 1)
        return cls(hi, lo, spec, plan, _mkb=mkb)

    @property
    def capacity(self) -> int:
        """Positional keys on device: longest fixed-length row hashable by
        `__call__` is capacity-1 (variable-length needs sentinel room)."""
        return int(self._key_hi.shape[1]) - 1

    def ensure(self, max_len: int) -> "Hasher":
        """A Hasher whose device planes cover rows up to `max_len` tokens
        (same keys -- Philox streams extend, never rewrite)."""
        if self.capacity >= _even(max_len + 2):
            return self
        if self._mkb is None:
            raise ValueError("cannot grow a Hasher detached from its key "
                             "buffer (rebuild via Hasher.from_spec)")
        return Hasher.from_keys(self._mkb, self.spec, max_len=max_len,
                                plan=self.plan)

    def with_plan(self, plan: HashPlan) -> "Hasher":
        return Hasher(self.key_hi, self.key_lo, self.spec, plan, _mkb=self._mkb)

    # -- pure JAX call path --------------------------------------------------

    def _required_width(self, n: int) -> int:
        return max(2, _even(n + 1) if self.spec.variable_length else _even(n))

    def __call__(self, tokens, lengths=None):
        """Hash (..., N) uint32/int32 tokens -> (..., K) uint32 hashes
        (out_bits=32) or (..., K, 2) uint32 (hi, lo) limbs of the family's
        64-bit surface (out_bits=64; hi == the 32-bit hash, jnp has no
        native uint64 -- integer families: the mod-2^64 accumulator; GF
        families: (hash32, acc_hi), DESIGN.md §11).

        Pure JAX: no host syncs, no numpy -- safe under jit/vmap/shard_map.
        `lengths` (optional, variable_length specs only) gives per-row token
        counts for the paper's append-1 policy; default is full rows.
        """
        out = self._hash_limbs(tokens, lengths)
        if self.spec.out_bits == 32:
            return out[..., 0]
        return out

    def _hash_limbs(self, tokens, lengths=None, mod_m=None):
        """Shared pure-JAX body of `__call__`/`probe_indices`: (..., N)
        tokens -> (..., K, 2) epilogue slots. Without mod_m the slots are
        the (hi, lo) accumulator limbs; with a `limbs.ModPlan` the backend
        fuses the Barrett reduction into its epilogue (DESIGN.md §2) and
        slot 0 is the probe index, slot 1 the finished 32-bit hash (a wide
        plan: the probe index's hi and lo limbs)."""
        spec = self.spec
        toks = jnp.asarray(tokens)
        batch_shape = toks.shape[:-1]
        N = toks.shape[-1]
        toks2 = toks.reshape((-1, N)).astype(U32)
        B = toks2.shape[0]
        W = self._required_width(N)
        if self.capacity < W:
            raise ValueError(
                f"Hasher capacity {self.capacity} < required width {W} for "
                f"rows of {N} tokens; use hasher.ensure({N})")
        toks2 = jnp.pad(toks2, ((0, 0), (0, W - N)))
        if lengths is None:
            code = jnp.full((B,), N if spec.variable_length else -(N + 1), I32)
        else:
            if not spec.variable_length:
                raise ValueError("lengths only apply with variable_length=True")
            code = jnp.asarray(lengths).reshape((-1,)).astype(I32)
        out = self._accumulate(toks2, code, W, mod_m)  # (B, K, 2)
        return out.reshape(*batch_shape, spec.n_hashes, 2)

    @property
    def _is_gf(self) -> bool:
        from .spec import FAMILIES

        return FAMILIES[self.spec.family].gf

    def _accumulate(self, toks2, code, W, mod_m=None):
        """(B, W) x length codes -> (B, K, 2) finished epilogue slots.

        Dispatches to the family's engine: the integer fused kernel / jnp
        oracle, or (gf traits) the carry-less twin -- SAME slot layout
        (DESIGN.md §11), so every consumer above this point is
        family-agnostic. GF keys are 32-bit: only the lo plane reaches
        the carry-less path (the hi plane is DCE'd under jit).
        """
        from ..kernels import gf_multihash as gfmh
        from ..kernels import multihash as mhk
        from ..kernels import ref

        gf = self._is_gf
        kh = self.key_hi[:, 1 : W + 1]
        kl = self.key_lo[:, 1 : W + 1]
        m1 = jnp.stack([self.key_hi[:, 0], self.key_lo[:, 0]], axis=1)
        plan = self.plan
        if plan.backend == "jnp":
            if gf:
                return ref.gf_multihash_ref(toks2, kl, code, m1,
                                            family=self.spec.family,
                                            mod_m=mod_m)
            return ref.multihash_ref(toks2, kh, kl, code, m1,
                                     family=self.spec.family, mod_m=mod_m)
        B, _ = toks2.shape
        bb = plan.block_b
        bn = min(plan.block_n, _even(W))
        Bp = -(-B // bb) * bb
        Wp = -(-W // bn) * bn
        toks_p = jnp.pad(toks2, ((0, Bp - B), (0, Wp - W)))
        # padding rows carry a dead fixed code (lm=0: every lane masked)
        code_p = jnp.pad(code, (0, Bp - B), constant_values=-1)
        kl_p = jnp.pad(kl, ((0, 0), (0, Wp - W)))
        if gf:
            out = gfmh.gf_multihash_blocks(
                toks_p, kl_p, code_p, m1, family=self.spec.family,
                block_b=bb, block_n=bn,
                interpret=(plan.backend == "interpret"), mod_m=mod_m)
        else:
            kh_p = jnp.pad(kh, ((0, 0), (0, Wp - W)))
            out = mhk.multihash_blocks(
                toks_p, kh_p, kl_p, code_p, m1, family=self.spec.family,
                block_b=bb, block_n=bn,
                interpret=(plan.backend == "interpret"), mod_m=mod_m)
        return out[:B]

    def bit_planes(self, tokens, lengths=None):
        """(..., N) tokens -> (..., K, 32) uint32 bit planes of the finished
        32-bit hash(es), LSB first: plane [..., k, j] = bit j of hash k.

        Pure JAX (jit/vmap/shard_map-safe). This is the output surface the
        quality battery's avalanche / bit-independence metrics consume
        (repro.quality.metrics) -- works for both out_bits=32 specs and
        out_bits=64 specs (the finished hash is the hi limb).
        """
        out = self(tokens, lengths)
        h = out if self.spec.out_bits == 32 else out[..., 0]
        return limbs.unpack_bits32(h)

    def shard_ids(self, tokens, n_shards: int, lengths=None):
        """(..., N) tokens -> (...,) int32 shard ids in [0, n_shards).

        Lemire multiply-shift range reduction ``(h * n_shards) >> 32`` on the
        32-bit hash: exactly uniform over residues up to the unavoidable
        floor(2^32/n) vs ceil rounding -- unlike ``h % n_shards``, whose low
        bits carry modulo bias. Pure JAX (jit/vmap-safe).
        """
        out = self(tokens, lengths)
        h = out[..., 0] if self.spec.out_bits == 32 else out[..., 0, 0]
        hi, _ = limbs.mul32_full(h, jnp.uint32(n_shards))
        return hi.astype(I32)

    def probe_indices(self, tokens, plan, lengths=None):
        """(..., N) tokens -> (..., K) uint32 Bloom probe indices in [0, m)
        -- or, for a wide plan (m >= 2^32), (..., K, 2) uint32 (hi, lo)
        limbs of each index: the family's full 64-bit surface mod
        `plan.m` -- the exact single-device `BloomFilter` formula (`h % m`
        on the uint64 hash_batch output, for every engine family). The
        Barrett digit reduction (`limbs.mod_u64`) runs FUSED in the
        backend's epilogue (the kernel `mod_m=` path: the accumulator never
        leaves registers before reducing), so this is pure JAX
        (jit/vmap/shard_map-safe, zero host syncs).

        plan: a `limbs.ModPlan` (or an int modulus, promoted at trace time).
        Requires an out_bits=64 spec: probe identity is defined on the full
        accumulator, not the finished 32-bit hash.
        """
        if self.spec.out_bits != 64:
            raise ValueError("probe_indices needs out_bits=64 (the mod-m "
                             "reduction consumes the full accumulator)")
        if not isinstance(plan, limbs.ModPlan):
            plan = limbs.ModPlan.for_modulus(plan)
        out = self._hash_limbs(tokens, lengths, mod_m=plan)
        return out if plan.wide else out[..., 0]

    # -- host-convenience batched engine -------------------------------------

    def hash_batch(
        self,
        tokens,
        *,
        lengths=None,
        variable_length: bool | None = None,
        out_bits: int | None = None,
        backend: str | None = None,
        block_b: int | None = None,
        block_n: int | None = None,
        autotune: bool = False,
    ) -> np.ndarray:
        """Batched multi-hash over host data: K hashes of every row, ONE pass.

        The (B, N) dense or ragged-list input is hashed by all K functions in
        a single fused kernel/jit launch (DESIGN.md §3/§6); the variable-
        length policy, m1 add, and final >>32 happen inside the launch.
        Returns (B, K) uint32 (out_bits=32) or uint64 (out_bits=64).

        backend: 'pallas' (TPU kernel), 'interpret' (kernel body on CPU),
          'jnp' (fused XLA oracle -- default off-TPU), 'host' (vectorized
          numpy uint64; bit-identical, no jit -- the single-item fast path).
        Every non-host call issues exactly one launch
        (`kernels.ops.launch_count`).
        """
        spec = self.spec
        if self._mkb is None:
            raise ValueError("hash_batch needs the Hasher's key buffer "
                             "(construct via from_spec/from_keys)")
        variable_length = (spec.variable_length if variable_length is None
                           else variable_length)
        out_bits = spec.out_bits if out_bits is None else out_bits
        toks, ragged_lens = _stack_ragged(tokens)
        if lengths is None:
            if ragged_lens is not None and not variable_length:
                raise ValueError(
                    "ragged input requires variable_length=True (fixed-length "
                    "semantics are ambiguous for rows of different lengths); "
                    "pass a dense (B, N) array for fixed-length hashing")
            lengths = ragged_lens
        B, N = toks.shape
        mkb = self._mkb
        K = mkb.n_hashes
        if backend is None:
            backend = default_plan().backend

        # Padded width: room for the sentinel + the HM even-pad (DESIGN.md §3).
        n_req = _even(N + 2) if variable_length else _even(N)
        lens = hostref.encode_lengths(lengths, N, variable_length, B)

        from ..kernels import autotune as ktune

        if backend == "host":
            # same pow2 width bucketing as the device path: keeps the key
            # buffer's per-width memo bounded under ragged streaming (pow2 is
            # even, so the HM pairing constraint holds)
            n_h = ktune.pow2_at_least(n_req)
            toks_h = np.zeros((B, n_h), np.uint32)
            toks_h[:, :N] = toks
            if self._is_gf:
                # carry-less twin: 32-bit keys = lo plane of the streams;
                # returns the engine's h64 = (hash32 << 32) | acc_hi surface
                acc = hostref.gf_multilinear_multi_np(
                    toks_h, lens, mkb.planes(n_h + 1)[1], family=spec.family)
            else:
                acc = hostref.multilinear_multi_np(
                    toks_h, lens, mkb.stacked_u64(n_h + 1),
                    family=spec.family)
            if out_bits == 64:
                return acc
            return (acc >> np.uint64(32)).astype(np.uint32)

        from ..kernels import ops as kops

        if block_b is None or block_n is None:
            # measure only on explicit opt-in: a default call must never block
            # on a compile+time sweep (best_blocks still consults the persisted
            # cache, so tuned processes get measured shapes for free)
            bb, bn = ktune.best_blocks(spec.family, B, n_req, K, backend,
                                       measure=bool(autotune))
            block_b = block_b or bb
            block_n = block_n or bn
        # Bucket padded shapes to powers of two of blocks so ragged workloads
        # hit a bounded jit cache instead of recompiling per batch shape
        # (same pow2 bucketing as the autotune cache keys -- single helper).
        Bp = block_b * ktune.pow2_at_least(-(-B // block_b))
        Np = block_n * ktune.pow2_at_least(-(-n_req // block_n))
        toks_p = np.zeros((Bp, Np), np.uint32)
        toks_p[:B, :N] = toks
        lens_p = np.full(Bp, -(Np + 1) if not variable_length else 0, np.int32)
        lens_p[:B] = lens
        kh, kl = mkb.planes(Np + 1)
        m1 = np.stack([kh[:, 0], kl[:, 0]], axis=1)

        out = np.asarray(kops.multihash(
            jnp.asarray(toks_p), jnp.asarray(kh[:, 1:]), jnp.asarray(kl[:, 1:]),
            jnp.asarray(lens_p), jnp.asarray(m1),
            family=spec.family, block_b=block_b, block_n=block_n,
            backend=backend,
        ))[:B]
        if out_bits == 64:
            return (out[:, :, 0].astype(np.uint64) << np.uint64(32)) | out[:, :, 1]
        return out[:, :, 0]

    # -- streaming (two-level UMAC-style tree; see streaming.py) --------------

    def stream(self, chunk_words: int = 1024, max_chunks: int = 4096):
        """Fresh incremental-fingerprint state (see `streaming.StreamState`)."""
        from . import streaming

        return streaming.init_stream(self, chunk_words, max_chunks)

    def update(self, state, tokens):
        """Absorb a 1-D uint32 token block into the stream (pure JAX)."""
        from . import streaming

        return streaming.update(self, state, tokens)

    def digest(self, state):
        """Finalize: (2,) uint32 (hi, lo) limbs of the 64-bit fingerprint."""
        from . import streaming

        return streaming.digest(self, state)

    def digest_int(self, state) -> int:
        """Host convenience: `digest` as a python int (one device sync).
        Re-checks the max_chunks bound on concrete counters (jit-driven
        updates carry tracers, so `update` cannot check in-graph)."""
        from . import streaming

        streaming._check_overflow(state)
        hi, lo = np.asarray(self.digest(state))
        return (int(hi) << 32) | int(lo)

    def sharded(self, mesh=None, axis: str = "data"):
        """Scale this Hasher out over a mesh data axis: a `ShardedHasher`
        (repro.hash.distributed) partitioning every batch over `axis`.
        Results are bit-identical to this Hasher; a 1-device mesh (the CPU
        CI runner) runs the same shard_map code path degenerately."""
        from .distributed import ShardedHasher

        return ShardedHasher(self, mesh, axis)

    # -- misc ----------------------------------------------------------------

    def __repr__(self):
        return (f"Hasher({self.spec}, plan={self.plan}, "
                f"capacity={self.capacity})")


def _hasher_flatten(h: Hasher):
    return (h.key_hi, h.key_lo), (h.spec, h.plan, h._mkb)


def _hasher_unflatten(aux, children):
    spec, plan, mkb = aux
    key_hi, key_lo = children
    return Hasher(key_hi, key_lo, spec, plan, _mkb=mkb)


jax.tree_util.register_pytree_node(Hasher, _hasher_flatten, _hasher_unflatten)
