"""Plain reference of the configurations' semantics, in numpy, written from
the definitions and not from the program (it imports nothing of it).

The hash (Lemire & Kaser, MULTILINEAR with the variable-length append-1
rule, 64-bit accumulator): for a string s_0..s_{L-1} of 32-bit tokens and
one function's keys m_0, m_1, ...,

    h(s) = m_0 + sum_{i<L} m_{i+1} s_i + m_{L+1} * 1    (mod 2^64)

The keys of function j of a filter with seed S are the 64-bit outputs of
numpy's Philox generator keyed on S xor (j * 0x9E3779B97F4A7C15), in
order: the key schedule the configuration names. A Bloom filter with m
bits and k functions sets, and tests, the bits h_j(s) mod m.

A filter does not start empty: it holds the state of a deployment part-way
through its corpus, as if `preload_items` random items had been added. Bit
i (any position below 2^64) of the preloaded state is set iff

    F(F(lo xor s_1) + s_2 + hi * 0x9E3779B9) < p * 2^32    (mod 2^32),

where lo = i mod 2^32 and hi = floor(i / 2^32) are the position's two
32-bit words, F is MurmurHash3's 32-bit finalizer, (s_1, s_2) come from
the run seed and p = 1 - exp(-k * preload_items / m) is the share of bits
such a filter holds. Below 2^32 (hi = 0) that is F(F(i xor s_1) + s_2).
The benchmark writes that state into the program's filter; the reference
computes it from this definition, at the positions it reads.

The control computes the same hash in the precision below (a 32-bit
accumulator: every product and sum mod 2^32), the step a faster kernel
would be tempted to take; it breaks the family the configuration states.
"""
from __future__ import annotations

import math

import numpy as np

GOLDEN64 = 0x9E3779B97F4A7C15
HI_MIX = 0x9E3779B9  # weight of a position's high word in the preload
U64 = np.uint64


def stream_keys(seed: int, j: int, n: int) -> np.ndarray:
    """First `n` 64-bit keys of function j (index 0 is m_0)."""
    s = (int(seed) ^ (j * GOLDEN64)) % (1 << 64)
    gen = np.random.Generator(np.random.Philox(key=np.uint64(s)))
    return gen.integers(0, 2**64, size=n, dtype=np.uint64)


class Keys:
    """k key streams, grown on demand (the Philox stream is a pure function
    of its index, so a longer draw extends a shorter one)."""

    def __init__(self, seed: int, k: int):
        self.seed, self.k = int(seed), int(k)
        self.u64 = np.zeros((self.k, 0), U64)

    def ensure(self, n: int) -> np.ndarray:
        if self.u64.shape[1] < n:
            n = max(n, 2 * self.u64.shape[1])
            self.u64 = np.stack([stream_keys(self.seed, j, n)
                                 for j in range(self.k)])
        return self.u64


def acc64(keys: Keys, row: np.ndarray) -> np.ndarray:
    """(k,) uint64 accumulators of one token row."""
    L = len(row)
    kk = keys.ensure(L + 2)
    with np.errstate(over="ignore"):
        dot = kk[:, 1:L + 1] @ row.astype(U64)
        return kk[:, 0] + dot + kk[:, L + 1]


def acc32(keys: Keys, row: np.ndarray) -> np.ndarray:
    """The control: (k,) accumulators computed mod 2^32 (low key words)."""
    L = len(row)
    lo = keys.ensure(L + 2).astype(np.uint32)
    with np.errstate(over="ignore"):
        dot = lo[:, 1:L + 1] @ row.astype(np.uint32)
        return (lo[:, 0] + dot + lo[:, L + 1]).astype(U64)


def probes(keys: Keys, rows, m: int, control: bool = False) -> np.ndarray:
    """(len(rows), k) int64 bit positions h_j(row) mod m."""
    f = acc32 if control else acc64
    out = np.zeros((len(rows), keys.k), np.int64)
    for i, r in enumerate(rows):
        out[i] = (f(keys, r) % U64(m)).astype(np.int64)
    return out


def fmix32(h: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer, elementwise on uint32."""
    h = h.astype(np.uint32)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def preload_params(cfg: dict, seed: int) -> "tuple[int, int, int]":
    """(s_1, s_2, threshold) of the preloaded state of a run."""
    m, k = int(cfg["m_bits"]), int(cfg["k"])
    p = 1.0 - math.exp(-k * int(cfg["preload_items"]) / m)
    s1, s2 = np.random.default_rng([int(seed), 0xF111]).integers(
        0, 2**32, 2, dtype=np.uint64).tolist()
    return int(s1), int(s2), min(2**32 - 1, int(round(p * 2**32)))


def preloaded(cfg: dict, seed: int, pos: np.ndarray) -> np.ndarray:
    """Whether the preloaded state holds the bits at int64 positions `pos`
    (any position from 0 to 2^63; the state holds none at or past m)."""
    s1, s2, thr = preload_params(cfg, seed)
    pos = np.asarray(pos, np.int64)
    lo = (pos & 0xFFFFFFFF).astype(np.uint32)
    hi = (pos >> 32).astype(np.uint32)
    h = fmix32(lo ^ np.uint32(s1))
    with np.errstate(over="ignore"):
        h = fmix32(h + np.uint32(s2) + hi * np.uint32(HI_MIX))
    return h < np.uint32(thr)


class Bloom:
    """Bloom filter of m bits and k functions over token strings, from the
    preloaded state `base(pos)` (empty where None). The bits set since the
    preload are kept as a sorted int64 array, so that m may be any size."""

    def __init__(self, m: int, k: int, seed: int, control: bool = False,
                 base=None):
        self.m, self.k = int(m), int(k)
        self.keys = Keys(seed, k)
        self.control = control
        self.base = base
        self.held = np.zeros(0, np.int64)  # bits set since the preload

    def probes(self, rows) -> np.ndarray:
        return probes(self.keys, rows, self.m, self.control)

    def _added(self, pos: np.ndarray) -> np.ndarray:
        if not len(self.held):
            return np.zeros(pos.shape, bool)
        i = np.minimum(np.searchsorted(self.held, pos), len(self.held) - 1)
        return self.held[i] == pos

    def bits_at(self, pos: np.ndarray) -> np.ndarray:
        pos = np.asarray(pos, np.int64)
        held = self._added(pos)
        return held if self.base is None else held | self.base(pos)

    def check_and_add(self, rows) -> np.ndarray:
        """True where a row was absent; every verdict is taken against the
        state before the batch (pre-batch verdicts), then all rows are set."""
        if not len(rows):
            return np.zeros(0, bool)
        p = self.probes(rows)
        new = ~self.bits_at(p).all(axis=1)
        fresh = np.unique(p)
        fresh = fresh[~self._added(fresh)]
        self.held = np.insert(self.held, np.searchsorted(self.held, fresh),
                              fresh)
        return new


def expected_new(keys: np.ndarray, wave: np.ndarray) -> np.ndarray:
    """Exact dedup, pre-batch: item i (content `keys[i]`, offered in wave
    `wave[i]`, waves in order) is new iff no earlier wave offered its
    content."""
    _, inv = np.unique(keys, return_inverse=True)
    first = np.full(inv.max() + 1 if len(inv) else 0, np.iinfo(np.int64).max)
    np.minimum.at(first, inv, wave)
    return first[inv] == wave
