"""The systems under test, built from a configuration file. A configuration
names its `system`; each kind here builds the program's own object through
its public constructor and exposes one call (`call`, the timed entry) and
the state the correctness check reads back once the window has closed.

This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def _mesh(chips: int):
    from repro.parallel.sharding import data_mesh

    return data_mesh(chips)


def _fmix32(h):
    import jax.numpy as jnp

    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _block(params, lo0, hi0, n: int):
    """The preloaded state's bits at the n positions from hi0 * 2^32 + lo0
    (the start's two uint32 words), as `bench/reference.py` defines them,
    with each position's uint32 offset from the start."""
    import jax
    import jax.numpy as jnp

    s1, s2, thr = params
    i = jax.lax.iota(jnp.uint32, n)
    lo = lo0 + i
    hi = hi0 + (lo < lo0).astype(jnp.uint32)  # carry into the high word
    h = _fmix32(_fmix32(lo ^ s1) + s2 + hi * jnp.uint32(reference.HI_MIX))
    return h < thr, i


def _params(cfg: dict, seed: int) -> tuple:
    return tuple(np.uint32(v) for v in reference.preload_params(cfg, seed))


def preload_block(cfg: dict, seed: int, start: int, n: int):
    """(n,) uint8: the preloaded state's bits at positions start to
    start + n - 1 (any start below 2^64 - n), unmasked by m, computed on
    the default device in one jitted call."""
    import jax
    import jax.numpy as jnp

    fill = jax.jit(lambda p, lo, hi: _block(p, lo, hi, n)[0].astype(jnp.uint8))
    return fill(_params(cfg, seed), np.uint32(start & 0xFFFFFFFF),
                np.uint32(start >> 32))


def preload(filt, cfg: dict, seed: int) -> None:
    """Write the preloaded state that `bench/reference.py` defines into a
    `DeviceShardedBloom`'s bit array, in one jitted call: each shard makes
    its own part from its global start, and zeroes its bits at and past m."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    m, ax, m_local = int(cfg["m_bits"]), filt.axis, _m_local(filt)
    starts = range(0, filt.bits.shape[0], m_local)
    words = np.array([[s & 0xFFFFFFFF, s >> 32, min(max(m - s, 0), m_local)]
                      for s in starts], np.uint32)  # start, bound of a shard

    def make(params, w):
        bit, i = _block(params, w[0, 0], w[0, 1], m_local)
        return (bit & (i < w[0, 2])).astype(jnp.uint8)

    fill = jax.jit(jax.shard_map(make, mesh=filt.mesh,
                                 in_specs=(P(), P(ax)), out_specs=P(ax)),
                   out_shardings=filt.bits.sharding)
    filt._bits = fill(_params(cfg, seed), words)


def _m_local(filt) -> int:
    """Bits a shard of the filter holds (its bit array is D of them)."""
    return filt.bits.shape[0] // filt.mesh.shape[filt.axis]


def split(pos: np.ndarray, m_local: int) -> "tuple[np.ndarray, np.ndarray]":
    """int64 global positions as (owner shard, local index), both int32."""
    pos = np.asarray(pos, np.int64)
    owner = pos // m_local
    return owner.astype(np.int32), (pos - owner * m_local).astype(np.int32)


def _reader(mesh, ax):
    """jit of (bits, owner, local) -> each position's bit, read on its
    owner shard and summed over the shards (so replicated)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def read(bits, owner, local):
        mine = owner == jax.lax.axis_index(ax)
        got = jnp.where(mine, bits[jnp.where(mine, local, 0)], 0)
        return jax.lax.psum(got.astype(jnp.int32), ax)

    return jax.jit(jax.shard_map(read, mesh=mesh, in_specs=(P(ax), P(), P()),
                                 out_specs=P()))


class BloomDedup:
    """`device_sharded_bloom`: corpus dedup through
    `DeviceShardedBloom.check_and_add_batch` (True = new item), from the
    preloaded state of the run's seed."""

    entry = "check_and_add_batch"

    def __init__(self, cfg: dict, chips: int, seed: int):
        from repro.hash import DeviceShardedBloom

        self.filt = DeviceShardedBloom(
            n_items=int(cfg["n_items"]), fp_rate=float(cfg["fp_rate"]),
            seed=int(cfg["seed"]), mesh=_mesh(chips),
            probe_transport=cfg["transport"], family=cfg["family"])
        if (self.filt.m, self.filt.k) != (int(cfg["m_bits"]), int(cfg["k"])):
            raise SystemExit(
                f"the program sized m={self.filt.m} k={self.filt.k}, the "
                f"configuration states m={cfg['m_bits']} k={cfg['k']}")
        preload(self.filt, cfg, seed)
        self.m_local = _m_local(self.filt)
        self._read = _reader(self.filt.mesh, self.filt.axis)

    def call(self, items) -> np.ndarray:
        return np.asarray(self.filt.check_and_add_batch(items))

    def bits_at(self, pos: np.ndarray) -> np.ndarray:
        """The filter's bits at int64 global positions below m, read back
        from the device in the order asked: each shard gathers the
        positions it owns, and a sum over the shards brings them back."""
        import jax.numpy as jnp

        shape, pos = np.shape(pos), np.asarray(pos, np.int64).ravel()
        if pos.size and (pos.min() < 0 or pos.max() >= self.filt.m):
            raise ValueError(f"bit positions must lie in [0, {self.filt.m})")
        owner, local = split(pos, self.m_local)
        size = 1 << max(10, (len(pos) - 1).bit_length())  # few shapes
        owner = np.pad(owner, (0, size - len(pos)), constant_values=-1)
        local = np.pad(local, (0, size - len(pos)))
        got = self._read(self.filt.bits, jnp.asarray(owner), jnp.asarray(local))
        return (np.asarray(got)[:len(pos)] != 0).reshape(shape)

    def state(self) -> dict:
        return {"bits_at": self.bits_at}


SYSTEMS = {"device_sharded_bloom": BloomDedup}


def build(cfg: dict, chips: int, seed: int):
    try:
        kind = SYSTEMS[cfg["system"]]
    except KeyError:
        raise SystemExit(f"unknown system {cfg.get('system')!r}; "
                         f"have {sorted(SYSTEMS)}") from None
    return kind(cfg, chips, seed)
