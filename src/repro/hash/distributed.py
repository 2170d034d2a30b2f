"""Multi-device scale-out for the `Hasher` engine: `shard_map` hashing and a
device-sharded Bloom filter.

The paper's throughput claim (0.2 cycles/byte) only matters at system scale
if the consumers scale with the kernel. This module partitions the *batch*
axis of every hashing workload across a mesh 'data' axis (Thorup's framing:
strongly universal hashing IS the load-balancing primitive, so the work
splits uniformly by construction):

- `ShardedHasher` -- wraps a `Hasher`; `__call__`/`shard_ids` are pure JAX
  `shard_map` regions over the data axis (zero host syncs, trace-asserted),
  and `hash_batch` is the host-convenience twin. Hashing is row-independent,
  so every sharded result is BIT-IDENTICAL to the single-device `Hasher`
  after gather -- pinned by tests on a mesh of size 1 (the CPU CI path: same
  code, degenerate mesh) and on 8 fake devices in a subprocess.
- `DeviceShardedBloom` -- each device owns a contiguous `1/D` range of the
  global bit array. Probe indices use the SAME `h mod m` formula as the
  single-device `BloomFilter` -- computed IN-GRAPH by the `limbs.mod_u64`
  Barrett digit reduction on each device's own accumulator limbs -- so
  membership decisions are bit-identical by construction and admission
  never round-trips through the host. Item -> home-shard routing for load
  accounting uses the Lemire `(h*n)>>32` reduction from
  `repro.hash.sharding`.

How probes move between devices is the filter's `probe_transport`, one
of two kinds (DESIGN.md section 7).  The default `"routed"` buckets each
device's (B/D, k) probe indices by owning bit range and exchanges ONLY the
owned probes with one `jax.lax.all_to_all` (~1/D the bytes of
`"all_gather"`, which replicates the full (B, k) matrix and is also the
routed kind's overflow repair).  Both move each probe as an (owner,
local) int32 pair -- its shard and its index inside the shard -- so m may
pass 2^32 while each shard stays below 2^31 bits.  Both are bit-identical
to the single-device `BloomFilter` -- the transport moves the same global
probe set, never changes it.  Collective layout: `add` is one fused launch
with zero psums and ZERO host syncs; `contains` and the fused
`check_and_add_batch` admission add exactly ONE psum.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import limbs
from ..parallel.sharding import data_mesh, mesh_axis_size
from .hasher import Hasher, _stack_ragged
from .service import ShardReply
from .spec import HashSpec

I32 = jnp.int32
U8 = jnp.uint8
# host spans of the admission call: TraceMe events on the profiler's host
# plane (the device's clock), about a microsecond each when tracing is off
_span = jax.profiler.TraceAnnotation


def _bucket_shape(B: int, N: int, D: int) -> "tuple[int, int]":
    """pow2 bounded-trace bucket for sharded launches: (Bp, Np) with the
    width rounded to the next power of two and rows to D * pow2(ceil(B/D))
    -- the D multiple makes the pure call's pad-to-multiple-of-D a no-op,
    so jit caches key on bucketed shapes only. Single source of the policy
    for `ShardedHasher.hash_batch`, and of the rows that
    `DeviceShardedBloom._stage` buckets to (its widths are its groups')."""
    from ..kernels.autotune import pow2_at_least

    return D * pow2_at_least(max(1, -(-B // D))), pow2_at_least(max(N, 1))


_LADDER_FLOOR = 32  # a device's rows in the ladder's last rung


def _ladder(Bp: int, D: int) -> "tuple[int, ...]":
    """Where the length order of a (Bp,)-row bucket over D devices may be
    cut in two: after Bp/2, 3Bp/4, 7Bp/8, ... rows, the longer part halving
    down to 32 x D rows. None where Bp <= 32 x D. Depends on (Bp, D)
    alone, and every part is a multiple of D rows."""
    floor = _LADDER_FLOOR * D
    cuts, tail = [], Bp // 2
    while Bp > floor and tail >= floor:
        cuts.append(Bp - tail)
        tail //= 2
    return tuple(cuts)


@jax.tree_util.register_pytree_node_class
class TokenArena:
    """A staged call's tokens: row groups laid back to back, device-major.

    `data` is the (D, S_local) uint32 arena, sharded on the data axis, and
    `groups` the static ((rows, width), ...) of each group on one device:
    device d's part holds, group after group, its (rows, width) block of
    every group. A pytree whose only leaf is `data`, so the group shapes
    key the jit caches of the admission surfaces."""

    def __init__(self, data, groups: "tuple[tuple[int, int], ...]"):
        self.data = data
        self.groups = groups

    def tree_flatten(self):
        return (self.data,), self.groups

    @classmethod
    def tree_unflatten(cls, groups, children):
        return cls(children[0], groups)

    def blocks(self, lens):
        """Inside the `shard_map`: each group's (rows, width) tokens and
        (rows,) lengths, cut from this device's part by static slices."""
        flat, t, r = self.data.reshape(-1), 0, 0
        for b, w in self.groups:
            yield flat[t:t + b * w].reshape(b, w), lens[r:r + b]
            t, r = t + b * w, r + b


class Staged(NamedTuple):
    """What `DeviceShardedBloom._stage` hands the device: the token arena,
    the (Bp,) int32 lengths and bool row-valid mask in staged-row order
    (device-major, then group by group), the true batch size, and each
    item's staged row (None where staged rows are the items' own order)."""

    tokens: TokenArena
    lens: jax.Array
    valid: jax.Array
    n: int
    rows: "np.ndarray | None"

    def pick(self, out) -> np.ndarray:
        """The items' entries of a (Bp,) per-staged-row output, in the
        items' order."""
        out = np.asarray(out)
        return out[:self.n] if self.rows is None else out[self.rows]


# routed bucket capacity: strong universality spreads a device's P probes
# uniformly over the D owners, so a bucket expects P/D; the factor is the
# headroom and the slack covers small P. Module constants, not options: a
# test shrinks them to force the overflow repair.
_CAPACITY_FACTOR = 1.25
_CAPACITY_SLACK = 16


def _capacity(n_probes: int, D: int) -> int:
    """Static per-destination bucket capacity of the routed exchange for a
    device's `n_probes` probes over D owners (jit needs fixed shapes).
    Clamped to n_probes (a bucket can never need more), so a 1-device mesh
    is structurally overflow-free."""
    cap = -(-int(n_probes * _CAPACITY_FACTOR) // D)
    return max(1, min(int(n_probes), cap + _CAPACITY_SLACK))


class ShardedHasher:
    """A `Hasher` scaled out over a mesh data axis.

    The wrapped hasher's key planes are replicated (they are small: K x cap
    uint32 pairs); the (B, N) token batch is partitioned over `axis`, each
    device runs the fused K-hash engine on its B/D rows, and results gather
    back along the same axis. Because every hash is a pure function of its
    own row, the gathered output is bit-identical to the single-device
    engine -- sharding changes the schedule, never the values (the same
    associativity argument as the kernel's block tiling, DESIGN.md section 2).

    A mesh of size 1 (the CPU CI runner) runs the identical `shard_map` code
    path -- degrade is "the collective is over one device", not a branch.
    """

    def __init__(self, hasher: Hasher, mesh: Mesh | None = None,
                 axis: str = "data"):
        self.hasher = hasher
        self.mesh = data_mesh() if mesh is None else mesh
        if axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has axes {self.mesh.axis_names}, no {axis!r}")
        self.axis = axis
        ax = axis
        # jitted shard_map surfaces, built once: the hasher rides as a pytree
        # OPERAND (replicated in_spec), so capacity growth / new key material
        # never invalidates these traces beyond normal shape retraces.
        self._fn = jax.jit(jax.shard_map(
            lambda hs, t: hs(t), mesh=self.mesh,
            in_specs=(P(), P(ax)), out_specs=P(ax), check_vma=False))
        self._fn_len = jax.jit(jax.shard_map(
            lambda hs, t, l: hs(t, l), mesh=self.mesh,
            in_specs=(P(), P(ax), P(ax)), out_specs=P(ax), check_vma=False))
        self._ids_fns: dict = {}

    @property
    def n_shards(self) -> int:
        return mesh_axis_size(self.mesh, self.axis)

    @property
    def spec(self) -> HashSpec:
        return self.hasher.spec

    def ensure(self, max_len: int) -> "ShardedHasher":
        """Grow the wrapped hasher's key planes in place (same Philox
        streams extend bit-exactly; the shard_map traces are reused because
        the hasher is an operand, not a closure constant)."""
        self.hasher = self.hasher.ensure(max_len)
        return self

    # -- pure JAX surfaces ----------------------------------------------------

    def _pad_rows(self, toks2, lengths):
        """Pad the flattened (B, N) batch to a multiple of D rows. Padding
        rows hash to garbage that is sliced off after the gather; their
        length code is 0 (cheapest row) for variable-length specs."""
        B = toks2.shape[0]
        D = self.n_shards
        Bp = -(-max(B, 1) // D) * D
        toks_p = jnp.pad(toks2, ((0, Bp - B), (0, 0)))
        lens_p = None
        if lengths is not None:
            lens_p = jnp.pad(
                jnp.asarray(lengths).reshape((-1,)).astype(I32), (0, Bp - B))
        return toks_p, lens_p, B

    def __call__(self, tokens, lengths=None):
        """Sharded twin of `Hasher.__call__`: (..., N) tokens -> (..., K)
        uint32 or (..., K, 2) limbs, computed B/D rows per device. Pure JAX:
        composes under jit; zero host syncs (trace-asserted in tests)."""
        toks = jnp.asarray(tokens)
        batch_shape, N = toks.shape[:-1], toks.shape[-1]
        toks_p, lens_p, B = self._pad_rows(toks.reshape((-1, N)), lengths)
        if lens_p is None:
            out = self._fn(self.hasher, toks_p)
        else:
            out = self._fn_len(self.hasher, toks_p, lens_p)
        out = out[:B]
        K = self.spec.n_hashes
        if self.spec.out_bits == 32:
            return out.reshape(*batch_shape, K)
        return out.reshape(*batch_shape, K, 2)

    def shard_ids(self, tokens, n_shards: int, lengths=None):
        """Sharded twin of `Hasher.shard_ids`: Lemire-reduced routing ids,
        computed per device over the partitioned batch."""
        key = (int(n_shards), lengths is not None)
        fn = self._ids_fns.get(key)
        if fn is None:
            ax = self.axis
            if key[1]:
                body = lambda hs, t, l: hs.shard_ids(t, n_shards, l)  # noqa: E731
                specs = (P(), P(ax), P(ax))
            else:
                body = lambda hs, t: hs.shard_ids(t, n_shards)  # noqa: E731
                specs = (P(), P(ax))
            fn = self._ids_fns[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=specs, out_specs=P(ax),
                check_vma=False))
        toks = jnp.asarray(tokens)
        batch_shape, N = toks.shape[:-1], toks.shape[-1]
        toks_p, lens_p, B = self._pad_rows(toks.reshape((-1, N)), lengths)
        args = (self.hasher, toks_p) if lens_p is None else (
            self.hasher, toks_p, lens_p)
        return fn(*args)[:B].reshape(batch_shape)

    def probe_indices(self, tokens, plan, lengths=None):
        """Sharded twin of `Hasher.probe_indices`: (..., N) tokens ->
        (..., K) uint32 Bloom probe indices in [0, m) (a wide plan:
        (..., K, 2) hi and lo limbs), each device reducing
        its own B/D accumulators through the fused Barrett `mod_m` epilogue
        (`limbs.mod_u64`, DESIGN.md §2). Bit-identical to the single-device
        surface -- the reduction is per-row, sharding only changes the
        schedule. The `ModPlan` is frozen/hashable, so each modulus gets one
        cached shard_map trace (same policy as `shard_ids`).
        """
        if not isinstance(plan, limbs.ModPlan):
            plan = limbs.ModPlan.for_modulus(plan)
        key = (plan, lengths is not None)
        fn = self._ids_fns.get(key)
        if fn is None:
            ax = self.axis
            if key[1]:
                body = lambda hs, t, l: hs.probe_indices(t, plan, l)  # noqa: E731
                specs = (P(), P(ax), P(ax))
            else:
                body = lambda hs, t: hs.probe_indices(t, plan)  # noqa: E731
                specs = (P(), P(ax))
            fn = self._ids_fns[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=specs, out_specs=P(ax),
                check_vma=False))
        toks = jnp.asarray(tokens)
        batch_shape, N = toks.shape[:-1], toks.shape[-1]
        toks_p, lens_p, B = self._pad_rows(toks.reshape((-1, N)), lengths)
        args = (self.hasher, toks_p) if lens_p is None else (
            self.hasher, toks_p, lens_p)
        K = self.spec.n_hashes
        return fn(*args)[:B].reshape(*batch_shape, K, *(2,) * plan.wide)

    # -- host-convenience batched engine --------------------------------------

    def hash_batch(self, tokens, *, lengths=None,
                   out_bits: int | None = None) -> np.ndarray:
        """Sharded twin of `Hasher.hash_batch`: dense or ragged host items
        in, (B, K) uint32/uint64 numpy out, hashed B/D rows per device
        through the pure shard_map path. Bit-identical to the single-device
        `Hasher.hash_batch` (pinned on a size-1 mesh and on 8 fake devices).

        Shapes are bucketed to powers of two (same `pow2_at_least` policy as
        the single-device engine): ragged streaming workloads hit a BOUNDED
        set of shard_map traces instead of recompiling per batch shape.
        Width bucketing needs explicit per-row lengths, so it applies to
        variable-length specs (every streaming consumer); fixed-length
        callers hash dense uniform batches where N is naturally stable.
        """
        spec = self.spec
        out_bits = spec.out_bits if out_bits is None else out_bits
        toks, ragged_lens = _stack_ragged(tokens)
        if lengths is None:
            if ragged_lens is not None and not spec.variable_length:
                raise ValueError(
                    "ragged input requires variable_length=True; pass a "
                    "dense (B, N) array for fixed-length hashing")
            lengths = ragged_lens
        B, N = toks.shape
        Bp, Np = _bucket_shape(B, N, self.n_shards)
        if spec.variable_length:
            if lengths is None:
                lengths = np.full(B, N, np.int64)
            toks_w = np.zeros((B, Np), np.uint32)
            toks_w[:, :N] = toks
            toks, N = toks_w, Np
        if Bp != B:
            toks = np.vstack([toks, np.zeros((Bp - B, N), np.uint32)])
            if lengths is not None:
                lengths = np.concatenate(
                    [np.asarray(lengths).reshape(-1),
                     np.zeros(Bp - B, np.int64)])
        sharded = self
        if out_bits == 64 and spec.out_bits == 32:
            # widen the OUTPUT only: same key streams, full accumulators.
            # The widened twin is cached -- its jitted shard_map surfaces
            # must persist across calls like the primary ones.
            if self.hasher._mkb is None:
                raise ValueError("64-bit output needs the Hasher's key buffer")
            w = getattr(self, "_wide64", None)
            if w is None:
                w = self._wide64 = ShardedHasher(
                    Hasher.from_keys(self.hasher._mkb,
                                     spec.with_(out_bits=64),
                                     max_len=N, plan=self.hasher.plan),
                    self.mesh, self.axis)
            sharded = w
        sharded.ensure(N)
        out = np.asarray(sharded(
            jnp.asarray(toks),
            None if lengths is None else jnp.asarray(lengths)))[:B]
        if out_bits == 64:
            return (out[..., 0].astype(np.uint64) << np.uint64(32)) | out[..., 1]
        if spec.out_bits == 64:
            return out[..., 0]  # finished >>32 hash lives in the hi limb
        return out


def probe_owner_local(r, m: int, m_local: int, n_shards: int):
    """Probe residues r in [0, m) -> (owner, local) int32 arrays:
    owner = r // m_local, the shard that holds bit r, and local =
    r - owner * m_local, its index inside that shard.

    `r` is what `Hasher.probe_indices` gives: (...,) uint32 for m < 2^32,
    (..., 2) uint32 (hi, lo) limbs for m >= 2^32. Pure 32-bit ops, no
    division: the owner counts the shard edges d * m_local (0 < d <
    n_shards, below m) that r reaches -- a two-limb compare against
    literals -- and local is the low word of r - owner * m_local, which is
    exact because the difference lies below m_local < 2^31."""
    wide = m >= 1 << 32
    hi, lo = (r[..., 0], r[..., 1]) if wide else (None, r)
    owner = jnp.zeros(lo.shape, I32)
    for d in range(1, n_shards):
        edge = d * m_local
        if edge >= m:  # no residue reaches it: the shards past are empty
            break
        e_hi, e_lo = np.uint32(edge >> 32), np.uint32(edge & 0xFFFFFFFF)
        ge = lo >= e_lo
        if wide:
            ge = (hi > e_hi) | ((hi == e_hi) & ge)
        owner = owner + ge.astype(I32)
    local = lo - owner.astype(jnp.uint32) * np.uint32(m_local)
    return owner, local.astype(I32)


def _admission_surfaces(mesh: Mesh, axis: str, m: int,
                        plan: "limbs.ModPlan") -> dict:
    """The jitted `shard_map` surfaces of a `DeviceShardedBloom` of m bits
    over `mesh`'s `axis`, by attribute name: `_add_dev`, `_contains_dev`,
    `_admit_dev` (all_gather) and `_add_rt`, `_contains_rt`, `_admit_rt`
    (routed), each taking (bits, hasher, `TokenArena`, lengths, valid).
    Built apart from a filter, so a surface compiles for a described
    topology without allocating any bits."""
    D = mesh_axis_size(mesh, axis)
    m_local, ax = -(-m // D), axis

    def _pairs(hs, toks, lens, valid):
        """A device's `TokenArena` part -> (b, k) owner and local int32
        arrays over its b staged rows: the Barrett reduction of each
        group's accumulators, group after group, split by shard.
        Padding rows carry the owner sentinel D: owned by no device,
        their probes drop from every scatter and read as present
        (dropped on host)."""
        r = [hs.probe_indices(t, plan, n) for t, n in toks.blocks(lens)]
        owner, local = probe_owner_local(
            r[0] if len(r) == 1 else jnp.concatenate(r, axis=0),
            m, m_local, D)
        return jnp.where(valid[:, None], owner, I32(D)), local

    def _scatter(bits, local):
        """Set every bit at `local`; the drop index m_local (foreign,
        sentinel or unused probes) falls outside the shard
        (mode="drop")."""
        return bits.at[local.ravel()].set(U8(1), mode="drop")

    # -- all_gather transport: every device sees every (owner, local) pair

    def _gather(hs, toks, lens, valid):
        """A device's staged rows -> (B, k, 2) int32 (owner, local) pairs of
        every row: one all_gather of each device's pairs along the data
        axis."""
        return jax.lax.all_gather(
            jnp.stack(_pairs(hs, toks, lens, valid), axis=-1), ax,
            axis=0, tiled=True)

    def _local(p):
        """(..., 2) (owner, local) pairs -> (local index, owned mask)
        with other devices' probes sent to the drop slot m_local."""
        owned = p[..., 0] == jax.lax.axis_index(ax)
        return jnp.where(owned, p[..., 1], m_local), owned

    def _miss(bits, p):
        loc, owned = _local(p)
        probe = jnp.where(owned, bits[jnp.clip(loc, 0, m_local - 1)],
                          U8(1))
        return jax.lax.psum(
            jnp.sum((probe == 0).astype(I32), axis=1), ax)

    def add_body_dev(bits, hs, toks, lens, valid):
        loc, _ = _local(_gather(hs, toks, lens, valid))
        return _scatter(bits, loc)

    def contains_body_dev(bits, hs, toks, lens, valid):
        return _miss(bits, _gather(hs, toks, lens, valid)) == 0

    def admit_body_dev(bits, hs, toks, lens, valid):
        p = _gather(hs, toks, lens, valid)
        present = _miss(bits, p) == 0
        loc, _ = _local(p)
        return _scatter(bits, loc), ~present

    # -- routed transport: owner-bucketed all_to_all probe exchange ----

    def _route(hs, toks, lens, valid):
        """Bucket this device's (b, k) probes by owning device and
        exchange only owned probes: (recv_local, recv_row, overflow, b).

        Probes pack into a static (D, cap, 2) int32 send buffer of
        (local index, sender-local row) pairs, bucket d holding the
        probes device d owns. Compaction is SCATTER-FREE (CPU scatters
        serialize; this pack used to cost as much as the exchange): a
        transposed per-destination running count (cumsum along the
        contiguous axis), then a vectorized binary search -- bucket
        d's j-th slot holds the first flat probe index whose running
        count reaches j+1 -- so slots fill first-fit in flat-index
        order and every buffer builds from gathers alone. Unused
        capacity carries the drop slot m_local as its local index and
        the b sentinel as its row; padding rows' probes carry the owner
        sentinel D, the out-of-range bucket (HIGH, never negative: a
        negative bucket would wrap and alias real buckets). One tiled
        `all_to_all` then swaps bucket d to device d -- first-fit
        order guarantees each received bucket's rows are
        non-decreasing with the sentinel tail last, which is what lets
        `_miss_rt` reduce per-row misses without a scatter. Probes
        beyond `cap` never pack and raise the per-device overflow flag
        -- the host-side settle path repairs via all_gather."""
        owner, local = _pairs(hs, toks, lens, valid)
        b, k = owner.shape
        n_probes = b * k
        cap = _capacity(n_probes, D)
        dest = owner.reshape(n_probes)
        onehot = dest[None, :] == jnp.arange(D, dtype=I32)[:, None]
        pos = jnp.cumsum(onehot.astype(I32), axis=1)  # (D, n) running
        counts = pos[:, -1]
        overflow = jnp.any(counts > cap)
        si = jax.vmap(lambda c: jnp.searchsorted(
            c, jnp.arange(cap, dtype=I32) + 1))(pos)
        ok = jnp.arange(cap, dtype=I32)[None, :] < counts[:, None]
        sl = jnp.where(ok, local.reshape(n_probes)[
            jnp.clip(si, 0, n_probes - 1)], I32(m_local))
        sr = jnp.where(ok, si.astype(I32) // I32(k), I32(b))
        recv = jax.lax.all_to_all(
            jnp.stack([sl, sr], axis=-1), ax,
            split_axis=0, concat_axis=0, tiled=True)
        return recv[..., 0], recv[..., 1], overflow, b

    def _miss_rt(bits, recv_local, recv_row, b):
        """(Bp,) global miss counts: test received probes locally,
        total per-row misses, ONE psum across devices. A row's total
        miss count is 0 iff all k of its global bits are set --
        identical verdict to the all_gather membership test even when
        duplicate probe indices land in one bucket.

        The per-row reduction is scatter-free: `_route`'s first-fit
        pack means bucket s arrives with non-decreasing sender-local
        rows (sentinel b in the tail), so each row's misses are one
        contiguous run -- an exclusive prefix sum per bucket plus a
        vectorized binary search for the run edges turns the reduction
        into pure gathers, and block s's (b,) counts land at global
        rows [s*b, (s+1)*b) by plain reshape (device s only ever sends
        its own rows)."""
        probe = jnp.where(recv_local < m_local,
                          bits[jnp.minimum(recv_local, m_local - 1)],
                          U8(1))
        miss = (probe == 0).astype(I32)  # (D, cap)
        cs = jnp.concatenate(
            [jnp.zeros((D, 1), I32), jnp.cumsum(miss, axis=1)], axis=1)
        edges = jax.vmap(lambda r: jnp.searchsorted(
            r, jnp.arange(b + 1, dtype=I32)))(recv_row)
        blk = jnp.arange(D, dtype=I32)[:, None]
        counts = cs[blk, edges[:, 1:]] - cs[blk, edges[:, :-1]]
        return jax.lax.psum(counts.reshape(b * D), ax)

    def add_body_rt(bits, hs, toks, lens, valid):
        recv_l, _, overflow, _ = _route(hs, toks, lens, valid)
        return _scatter(bits, recv_l), overflow[None]

    def contains_body_rt(bits, hs, toks, lens, valid):
        recv_l, recv_row, overflow, b = _route(hs, toks, lens, valid)
        present = _miss_rt(bits, recv_l, recv_row, b) == 0
        return present, overflow[None]

    def admit_body_rt(bits, hs, toks, lens, valid):
        recv_l, recv_row, overflow, b = _route(hs, toks, lens, valid)
        present = _miss_rt(bits, recv_l, recv_row, b) == 0
        return _scatter(bits, recv_l), ~present, overflow[None]

    # the hasher rides as a replicated pytree operand (like ShardedHasher),
    # tokens/lengths/valid partition over the axis
    sm = lambda body, out_specs: jax.jit(jax.shard_map(  # noqa: E731
        body, mesh=mesh,
        in_specs=(P(ax), P(), P(ax), P(ax), P(ax)),
        out_specs=out_specs, check_vma=False))
    return {
        "_add_dev": sm(add_body_dev, P(ax)),
        "_contains_dev": sm(contains_body_dev, P()),
        "_admit_dev": sm(admit_body_dev, (P(ax), P())),
        # routed surfaces: same operand layout; overflow flags come back
        # per-device (out_spec P(axis) over a (1,) bool) so reading them
        # never adds a collective to the launch.
        "_add_rt": sm(add_body_rt, (P(ax), P(ax))),
        "_contains_rt": sm(contains_body_rt, (P(), P(ax))),
        "_admit_rt": sm(admit_body_rt, (P(ax), P(), P(ax))),
    }


class DeviceShardedBloom:
    """k-probe Bloom filter whose bit array is range-partitioned over the
    mesh data axis: device d owns global bits [d*m_local, (d+1)*m_local).

    Decision compatibility (pinned in tests): same (m, k, seed) parameters
    and the same global probe formula `h_j mod m` as the single-device
    `BloomFilter`, so the SET of global bits lit by any key sequence -- and
    therefore every membership decision -- is bit-identical; only bit
    *placement* is distributed. Storage is one device byte per bit (scatter/
    gather-native on the VPU; the packed-word layout of the host filter is a
    memory optimization this layer trades for collective-free scatters).
    m may pass 2^32; each shard's m_local = ceil(m/D) must stay below 2^31,
    the int32 local index.

    Probes are computed IN-GRAPH: each device hashes its B/D rows and
    reduces the (hi, lo) accumulator limbs mod m with the Barrett digit
    reduction (`limbs.mod_u64`, exact for every m < 2^63, as two limbs past
    2^32 -- DESIGN.md §2), then splits each residue into an (owner, local)
    int32 pair (`probe_owner_local`). How the pairs reach the devices that
    OWN those bits is `probe_transport`: the default `"routed"` buckets
    them by owner and exchanges only (local, row) pairs with one
    `jax.lax.all_to_all`; `"all_gather"` replicates the full (B, k) pair
    matrix. Any other value raises `ValueError`. A receiver sets and tests
    `local` as it is. Admission never leaves the device on either kind.

    Collective layout (per-transport bytes table in DESIGN.md §7):
      add_batch             one launch, one collective, ZERO psums and ZERO
                            host syncs (each device scatters only its owned
                            range; foreign/sentinel probes drop)
      contains_batch        one launch, one collective + ONE psum (routed:
                            miss counts reduced per global row id;
                            all_gather: per-device miss counts)
      check_and_add_batch   one fused launch, one collective + ONE psum
                            (verdicts against the pre-batch state, scatter)
    Item -> home-shard routing (`owner_shards`) uses the existing Lemire
    `(h*n)>>32` reduction from `repro.hash.sharding` for multi-host admission
    planning; probe ownership itself is the contiguous range map above.

    Routed bucket overflow: each bucket holds `_capacity` probes, a static
    shape, so a bucket past it truncates and raises an in-graph flag. The
    one policy is repair by replay through the all_gather surface. Add
    launches stay zero-sync by deferring the flag read -- the batch is
    queued and the flags of up to `_settle_every` pending adds materialize
    together at the next verdict-returning call (or `bits` read). Truncated
    scatters only ever light a SUBSET of the correct bits, so bit union
    makes the replay exact, no snapshot needed. A verdict-returning call
    reads its flag with its verdicts and reruns against the untouched
    pre-call bits.

    `stats` is the operator's view of the filter, plain ints since
    construction. `overflow_fallbacks`: routed calls replayed through
    all_gather. Counted per staged call of `add_batch`, `contains_batch`
    and `check_and_add_batch`: `calls`; `items`, the real rows B;
    `real_tokens`, the sum of the item lengths; `staged_tokens`, the slots
    of the call's row groups (rows x width, summed); `stage_groups`, the
    groups it launched (1 where the call stays one bucket, else 2).
    `staged_tokens / real_tokens` is the padding waste: the factor by
    which the staged, copied and hashed bytes exceed the input.
    `stage_reuses`: staged calls written into the kept arena;
    `stage_allocs`: staged calls that had to allocate one (the first call,
    and every change of the groups' shapes). Counted per launch, from
    shapes alone (no sync): `exchange_slots`, the slots of one device's
    routed send buffer (D x capacity, routed launches only), and
    `exchange_bytes`, the bytes of one device's send buffer on every
    launch, replays included (8 a slot or pair: routed, D x capacity
    (local, row) slots; all_gather, its (B/D) x k pairs).

    Host staging (`_stage`) cuts a call's length-sorted rows in two. Rows
    bucket to Bp = D x pow2(ceil(B/D)), as `_bucket_shape` gives; a stable
    argsort orders them by length (padding rows have length 0), and the
    order is cut at one rung of a fixed halving ladder (`_ladder`: after
    Bp/2, 3Bp/4, ... rows, the longer part halving down to 32 x D rows):
    the rung that stages the fewest slots, each part as wide as the power
    of two above its own longest row. So the longest 1/2, 1/4, ... of the
    rows share a group of their own, and the rest is not padded to their
    width. Each group's d-th 1/D goes to device d. The call stays one
    Bp x Np bucket, rows in their own order, where Bp <= 32 x D
    (serve-shaped batches) or where no cut stages fewer slots (rows of one
    width class, a dense (B, N) batch), so a call never stages more slots
    than that bucket. Two groups, not more: each group shape is a kernel
    of its own to trace and lower (about 1.5 s on a v5e host, in every
    process, since only the compiled program is cached). A compile is
    keyed on the groups' (rows, width), pow2 values, so traces stay
    bounded as under one bucket.

    The groups lie back to back in ONE kept host arena, device-major: a
    (D, S_local) uint32 `TokenArena` whose device-d part holds its block
    of every group in turn, so a call makes three host-to-device copies
    (arena, lengths, valid mask) whatever its group count. Inside the
    `shard_map` each device cuts its groups out by static slices, hashes
    them one by one and concatenates their (owner, local) probes in group
    order into the (B/D, k) a device routes, tests and scatters in one
    launch. Verdicts come back per staged row; `Staged.pick` maps them to
    the items through the argsort. The arena is reused while the groups'
    shapes hold (a new one is allocated when they change): each call
    writes every item's tokens once, straight into its staged row, and
    zeroes only what the last call wrote beyond the new lengths, so the
    staged bytes equal a fresh zero-padded stack. The device gets its own
    copy, so staged arrays held for an overflow replay never see a later
    call's rewrite.

    Spans (`jax.profiler.TraceAnnotation`, seen only under the profiler):
    `bloom.check_and_add_batch` (metadata `call`, `items`, `width` (the
    widest group), `groups`, `shards`, `m_bits`) holds
    `bloom.stage`, `bloom.launch`, `bloom.readback` and, on a routed
    overflow, `bloom.overflow_replay`; `bloom.stage` holds, in order,
    `bloom.stage.stack` (the item lengths and their order, and on a reuse
    the tokens written into the kept arena), `bloom.stage.pad` (on a reuse
    the stale row tails and rows zeroed; on a shape change the new arena
    allocated and the tokens written into its fresh pages) and
    `bloom.stage.to_device` (three host-to-device copies, plus the
    device-side copy of the tokens).
    """

    _settle_every = 8  # max deferred routed adds before flags materialize

    def __init__(self, n_items: int, fp_rate: float = 1e-3, seed: int = 0xB100,
                 mesh: Mesh | None = None, axis: str = "data",
                 probe_transport: str = "routed",
                 family: str = "multilinear"):
        import math

        if probe_transport not in ("routed", "all_gather"):
            raise ValueError(
                f"probe_transport {probe_transport!r} is neither 'routed' "
                "nor 'all_gather'")
        self.transport = probe_transport

        # same sizing as data.dedup.BloomFilter -- decision identity needs
        # identical (m, k) for identical inputs
        self.m = max(64, int(-n_items * math.log(fp_rate) / (math.log(2) ** 2)))
        self.k = max(1, int(self.m / n_items * math.log(2)))
        self.mesh = data_mesh() if mesh is None else mesh
        D = mesh_axis_size(self.mesh, axis)
        self.m_local = -(-self.m // D)
        if self.m_local >= 1 << 31:
            raise ValueError(
                f"m={self.m} bits over {D} shard(s) is {self.m_local} bits a "
                "shard, past the int32 local index (2^31): shard the filter "
                "over more devices; packed bits (32 to an int32 word) would "
                "lift the limit")
        # any engine family works: probes are `h % m` of the family's 64-bit
        # hash, taken in-graph by the fused mod_m epilogue
        self.sharded = ShardedHasher(Hasher.from_spec(HashSpec(
            family=family, n_hashes=self.k, out_bits=64,
            variable_length=True, seed=seed)), self.mesh, axis)
        self.axis = axis
        self.plan = limbs.ModPlan.for_modulus(self.m)
        m_pad = self.m_local * D
        self._rows = NamedSharding(self.mesh, P(axis))
        self._bits = jax.jit(lambda: jnp.zeros(m_pad, U8),
                             out_shardings=self._rows)()
        self._pending: list = []  # routed adds with unread overflow flags
        self.stats = {"overflow_fallbacks": 0, "calls": 0, "items": 0,
                      "real_tokens": 0, "staged_tokens": 0,
                      "stage_groups": 0,
                      "stage_reuses": 0, "stage_allocs": 0,
                      "exchange_slots": 0, "exchange_bytes": 0}
        # the kept host arena, its groups, the staged row of each length
        # rank, its row views, how far each row was written, and the device
        # copy of its last contents (`_stage`)
        self._buf = self._buf_groups = self._buf_ranks = None
        self._buf_rows = self._buf_lens = self._buf_fence = None

        vars(self).update(_admission_surfaces(
            self.mesh, axis, self.m, self.plan))

    @property
    def n_shards(self) -> int:
        return self.sharded.n_shards

    @property
    def bits(self) -> jnp.ndarray:
        """The (m_local * D,) uint8 global bit array (device-sharded; bits
        at and past m stay 0). A
        read settles any pending routed adds first, so observers always see
        repaired, `BloomFilter`-identical state."""
        self._settle()
        return self._bits

    def _settle(self) -> None:
        """Materialize the overflow flags of pending routed adds. Batches
        whose flag fired were truncated -- their scatters lit a SUBSET of
        the correct bits -- so replay exactly those through the all_gather
        surface (bit union repairs in place; adds already fully applied are
        untouched)."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        replay = [staged for flag, staged in pending
                  if bool(np.asarray(flag).any())]
        if not replay:
            return
        with _span("bloom.overflow_replay"):
            for st in replay:
                self._bits = self._replay(self._add_dev, st)

    def _prepare(self, items):
        """Stage `items` and count the launch they are staged for: the
        `Staged` call and the surface arguments after the bits."""
        st = self._stage(items)
        self._count_exchange(st.lens.shape[0], self.transport == "routed")
        return st, (self.sharded.hasher, *st[:3])

    def _replay(self, surface, st: Staged):
        """Rerun a routed call whose exchange overflowed through its
        all_gather `surface`, against the filter's current bits, and count
        it."""
        self.stats["overflow_fallbacks"] += 1
        self._count_exchange(st.lens.shape[0], routed=False)
        return surface(self._bits, self.sharded.hasher, *st[:3])

    def _count_exchange(self, rows: int, routed: bool) -> None:
        """Add one in-graph launch over `rows` staged rows to the exchange
        counters (class docstring), from shapes alone."""
        D = self.n_shards
        n = rows // D * self.k  # one device's probes
        if routed:
            slots = D * _capacity(n, D)
            self.stats["exchange_slots"] += slots
            self.stats["exchange_bytes"] += slots * 8
        else:
            self.stats["exchange_bytes"] += n * 8

    def _groups(self, lens):
        """(groups, order) for a call's (Bp,) padded lengths: the
        ((rows, width), ...) of each group on one device, and the stable
        length order of the rows, or None where the rows keep their own
        order in one Bp x Np bucket. The length order is cut in two at the
        `_ladder` rung that stages the fewest slots, each part as wide as
        the power of two above its own longest row; one bucket where there
        is no rung, or no cut stages fewer slots (rows of one width class,
        a dense batch)."""
        from ..kernels.autotune import pow2_at_least

        D, Bp = self.n_shards, len(lens)
        top = pow2_at_least(max(int(lens.max()), 1))
        cuts = _ladder(Bp, D)
        if cuts:
            order = np.argsort(lens, kind="stable")
            widths = [pow2_at_least(max(n, 1))
                      for n in lens[order[np.asarray(cuts) - 1]].tolist()]
            slots, c, w = min((c * w + (Bp - c) * top, c, w)
                              for c, w in zip(cuts, widths))
            if w < top:
                return ((c // D, w), ((Bp - c) // D, top)), order
        return ((Bp // D, top),), None

    def _rank_rows(self, groups) -> np.ndarray:
        """(Bp,) staged row of each length rank: a group's ranks go in D
        equal runs, the d-th to the group's block on device d. Staged row
        d x (Bp/D) + j is row j of device d's part of the arena."""
        D, R = self.n_shards, sum(b for b, _ in groups)
        out, off = [], 0
        for b, _ in groups:
            t = np.arange(b * D)
            out.append(t // b * R + off + t % b)
            off += b
        return np.concatenate(out)

    def _stage(self, items) -> Staged:
        """Stage host items for a launch (class docstring). Rows
        bucket to `_bucket_shape`'s pow2 row count Bp; `_groups` either
        keeps them in order in one Bp x Np bucket, or orders them by
        length and cuts them into two groups, each as wide as the power of
        two above its own longest row. Padding rows have length
        0 and are invalid -- their probes carry the owner sentinel D
        in-graph. Tokens go once into the kept host arena: a staged row
        holds its item in columns [0, len) and zeros elsewhere, as a fresh
        zero-padded stack would."""
        with _span("bloom.stage"):
            with _span("bloom.stage.stack"):
                if self._buf_fence is not None:
                    # the device has its copy of the last call's bytes
                    # before any of them is overwritten
                    self._buf_fence.block_until_ready()
                if isinstance(items, (list, tuple)):
                    src = [r if isinstance(r, np.ndarray) and r.ndim == 1
                           else np.atleast_1d(np.asarray(r)) for r in items]
                    lens = np.fromiter(map(len, src), np.int64, len(src))
                else:
                    src = np.atleast_2d(np.asarray(items))
                    lens = np.full(src.shape[0], src.shape[1], np.int64)
                B = len(lens)
                Bp, _ = _bucket_shape(B, 1, self.n_shards)
                lens_p = np.zeros(Bp, np.int32)
                lens_p[:B] = lens
                groups, order = self._groups(lens_p)
                reuse = self._buf is not None and self._buf_groups == groups
                ranks = self._buf_ranks if reuse else self._rank_rows(groups)
                at = None  # each item's staged row, where not its own
                if order is not None:
                    rows = np.empty(Bp, np.int64)
                    rows[order] = ranks
                    at = rows[:B]
                    lens_s = np.empty_like(lens_p)
                    lens_s[rows] = lens_p
                    lens_p = lens_s
                if reuse:
                    self._fill(src, at)
            with _span("bloom.stage.pad"):
                if reuse:
                    prev = self._buf_lens
                    stale = np.flatnonzero(prev > lens_p)
                    views = self._buf_rows
                    for i, lo, hi in zip(stale.tolist(),
                                         lens_p[stale].tolist(),
                                         prev[stale].tolist()):
                        views[i][lo:hi] = 0
                else:
                    self._alloc(groups, ranks)
                    self._fill(src, at)
                self._buf_lens = lens_p
                valid = np.zeros(Bp, bool)
                valid[slice(B) if at is None else at] = True
            st = self.stats
            st["calls"] += 1
            st["items"] += B
            st["real_tokens"] += int(lens.sum())
            st["staged_tokens"] += self.n_shards * sum(b * w
                                                       for b, w in groups)
            st["stage_groups"] += len(groups)
            st["stage_reuses" if reuse else "stage_allocs"] += 1
            with _span("bloom.stage.to_device"):
                self.sharded.ensure(max(w for _, w in groups))
                # a runtime may zero-copy host memory (the CPU client does,
                # for aligned arrays): the device-side copy is what no later
                # rewrite of the arena can reach. Each device's part goes
                # straight to it.
                toks = jnp.copy(jax.device_put(self._buf, self._rows))
                self._buf_fence = toks
                lens_d, valid_d = jax.device_put((lens_p, valid), self._rows)
                return Staged(TokenArena(toks, groups), lens_d, valid_d, B, at)

    def _alloc(self, groups, ranks) -> None:
        """A zeroed (D, S_local) host arena for `groups`, and the view of
        every staged row into it, in staged-row order."""
        D = self.n_shards
        self._buf = np.zeros((D, sum(b * w for b, w in groups)), np.uint32)
        self._buf_groups, self._buf_ranks = groups, ranks
        self._buf_rows = views = []
        for part in self._buf:
            off = 0
            for b, w in groups:
                views.extend(part[off:off + b * w].reshape(b, w))
                off += b * w

    def _fill(self, src, at) -> None:
        """Write item tokens into the kept arena, casting in place: a dense
        (B, N) array in one assignment where the rows keep their order,
        else row by row into each item's staged row (`at`)."""
        views = self._buf_rows
        if at is None:
            if isinstance(src, np.ndarray):
                (_, w), = self._buf_groups
                self._buf.reshape(-1, w)[:src.shape[0], :src.shape[1]] = src
                return
            at = range(len(src))
        else:
            at = at.tolist()
        for i, row in zip(at, src):
            views[i][:len(row)] = row

    def owner_shards(self, items) -> np.ndarray:
        """(B,) home shard per item via the Lemire multiply-shift reduction
        on the finished 32-bit hash (load-accounting/routing for multi-host
        admission; probe ownership is the contiguous range map)."""
        from .sharding import reduce_range

        h32 = (self.sharded.hash_batch(items)[:, 0]
               >> np.uint64(32)).astype(np.uint32)
        return reduce_range(h32, self.n_shards)

    def add_batch(self, items) -> None:
        """Admit a batch in ONE fused launch: hash + Barrett mod + probe
        exchange + owned-range scatter, all in-graph -- zero psums and
        ZERO host syncs (the routed overflow flag is deferred to the next
        settle point)."""
        if len(items) == 0:
            return
        st, args = self._prepare(items)
        if self.transport == "all_gather":
            self._bits = self._add_dev(self._bits, *args)
            return
        self._bits, flag = self._add_rt(self._bits, *args)
        self._pending.append((flag, st))
        if len(self._pending) >= self._settle_every:
            self._settle()

    def contains_batch(self, items) -> np.ndarray:
        """(B,) bool membership -- one fused launch, one collective + one
        psum; the only host transfer is the final (B,) verdict read (the
        routed overflow flag rides in the same transfer)."""
        if len(items) == 0:
            return np.zeros(0, bool)
        self._settle()
        st, args = self._prepare(items)
        if self.transport == "all_gather":
            return st.pick(self._contains_dev(self._bits, *args))
        verdict, flag = self._contains_rt(self._bits, *args)
        if bool(np.asarray(flag).any()):
            verdict = self._replay(self._contains_dev, st)
        return st.pick(verdict)

    def check_and_add_batch(self, items) -> np.ndarray:
        """(B,) admission mask in ONE fused launch + ONE psum: True where
        the item was not already present. Verdicts are evaluated against the
        pre-batch state (duplicates WITHIN a batch all admit -- the batched
        round-trip contract; stream items through `contains`+`add` per
        sub-batch when arrival-order dedup inside a batch matters)."""
        if len(items) == 0:
            return np.zeros(0, bool)
        with _span("bloom.check_and_add_batch") as span:
            self._settle()
            st, args = self._prepare(items)
            groups = st.tokens.groups
            span.set_metadata(call=self.stats["calls"], items=st.n,
                              width=max(w for _, w in groups),
                              groups=len(groups), shards=self.n_shards,
                              m_bits=self.m)
            routed = self.transport == "routed"
            with _span("bloom.launch"):
                if routed:
                    new_bits, admitted, flag = self._admit_rt(
                        self._bits, *args)
                else:
                    new_bits, admitted = self._admit_dev(self._bits, *args)
            with _span("bloom.readback"):
                overflow = routed and bool(np.asarray(flag).any())
                if not overflow:
                    admitted = st.pick(admitted)
            if overflow:
                # truncated exchange: discard the partial scatter/verdicts
                # and rerun against the untouched pre-call bits via all_gather
                with _span("bloom.overflow_replay"):
                    new_bits, admitted = self._replay(self._admit_dev, st)
                    admitted = st.pick(admitted)
            self._bits = new_bits
            return admitted

    def add(self, item) -> None:
        self.add_batch([np.atleast_1d(item)])

    def __contains__(self, item) -> bool:
        return bool(self.contains_batch([np.atleast_1d(item)])[0])


# ---------------------------------------------------------------------------
# admission-service backend adapter
# ---------------------------------------------------------------------------

class FilterShardBackend:
    """Adapts a batch filter to the admission service's shard protocol.

    Any object with `check_and_add_batch` / `contains_batch` / `add_batch`
    works: the host `data.dedup.BloomFilter` (arrival-order in-batch
    semantics -- the service's decision-identity reference) or a
    `DeviceShardedBloom` (one fused launch per call; verdicts against the
    pre-batch state, the documented batched-round-trip contract).

    Replies carry the paper's own integrity fingerprint
    (`ShardReply.for_payload`), and non-ping requests are IDEMPOTENT: the
    reply for each `req_id` is cached (bounded LRU), so a retry after a
    dropped reply returns the ORIGINAL verdict -- at-least-once delivery
    never flips an admit into a reject.
    """

    def __init__(self, filt, cache_size: int = 64):
        import collections

        self.filt = filt
        self._replies: "dict[int, ShardReply]" = collections.OrderedDict()
        self._cache_size = int(cache_size)
        self.calls = {"admit": 0, "contains": 0, "add": 0, "ping": 0,
                      "replayed": 0}

    def serve(self, request) -> ShardReply:
        if request.op == "ping":
            self.calls["ping"] += 1
            return ShardReply.for_payload(np.zeros(0, bool))
        if request.req_id and request.req_id in self._replies:
            self.calls["replayed"] += 1
            return self._replies[request.req_id]
        items = list(request.items)
        self.calls[request.op] += 1
        if request.op == "admit":
            payload = self.filt.check_and_add_batch(items)
        elif request.op == "contains":
            payload = self.filt.contains_batch(items)
        elif request.op == "add":
            self.filt.add_batch(items)
            payload = np.ones(len(items), bool)
        else:
            raise ValueError(f"unknown shard op {request.op!r}")
        reply = ShardReply.for_payload(payload)
        if request.req_id:
            self._replies[request.req_id] = reply
            while len(self._replies) > self._cache_size:
                self._replies.pop(next(iter(self._replies)))
        return reply


def bloom_shard_backends(
        n_shards: int, n_items: int, fp_rate: float = 1e-3,
        seed: int = 0xB100, *, mesh: Mesh | None = None,
) -> "list[FilterShardBackend]":
    """`n_shards` keyspace-partitioned Bloom backends for the admission
    service (each shard's filter sized for its 1/n share of the items; the
    service's Lemire routing keeps loads uniform by strong universality).

    With `mesh=` each shard's filter is a `DeviceShardedBloom` whose bits
    range-partition over the mesh data axis; verdicts are then against the
    pre-batch state (the batched contract) instead of the host filter's
    arrival order -- the service's per-shard batching makes both orders
    converge to the same filter state."""
    per = max(1, -(-int(n_items) // int(n_shards)))
    if mesh is not None:
        return [FilterShardBackend(DeviceShardedBloom(
                    n_items=per, fp_rate=fp_rate, seed=seed, mesh=mesh))
                for _ in range(int(n_shards))]
    from ..data.dedup import BloomFilter

    return [FilterShardBackend(BloomFilter(n_items=per, fp_rate=fp_rate,
                                           seed=seed))
            for _ in range(int(n_shards))]
