"""`DeviceShardedBloom._stage` writes into one kept host arena: what it
hands the device must equal, byte for byte, a fresh zero-padded staging of
the same items -- one bucket, or the length-sorted row groups of the
two length-sorted row groups -- over any sequence of calls on one filter: rows that
shrink and grow, batches smaller than the bucket, changes of the groups'
shapes and dense input; and no array it handed out may change when a
later call rewrites the arena."""
import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom
from repro.hash import distributed
from repro.parallel.sharding import data_mesh

RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x57A6E)))


def _ragged(lengths):
    return [RNG.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
            for n in lengths]


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _reference(items, D=1):
    """The staging that allocates afresh on every call, written out plainly:
    (arena, lengths, valid, n, staged row of each item, groups). Rows pad
    to Bp = D x pow2(ceil(B/D)) with empty invalid rows. Where Bp > 32 x D
    the length-sorted rows may be cut in two after Bp/2, 3Bp/4, ... rows
    (the longer part down to 32 x D rows): the cut that stages the fewest
    slots, each part as wide as the pow2 above its longest row, device d
    taking the d-th 1/D of each part. Without a cut that stages fewer slots
    the rows stay in order in one (Bp, pow2(longest)) bucket, Bp/D rows a
    device."""
    if isinstance(items, (list, tuple)):
        rows = [np.atleast_1d(np.asarray(r)).astype(np.uint32) for r in items]
    else:
        rows = list(np.atleast_2d(np.asarray(items)).astype(np.uint32))
    B = len(rows)
    Bp = D * _pow2(-(-B // D))
    lens = [len(r) for r in rows] + [0] * (Bp - B)
    order = sorted(range(Bp), key=lambda i: lens[i])  # a stable sort
    top = _pow2(max(max(lens), 1))
    best, tail = None, Bp // 2
    while Bp > 32 * D and tail >= 32 * D:
        c = Bp - tail
        w = _pow2(max(lens[order[c - 1]], 1))
        if w < top and (best is None or c * w + tail * top < best[0]):
            best = (c * w + tail * top, c, w)
        tail //= 2
    if best is None:
        order, sizes, widths = list(range(Bp)), [Bp], [top]
    else:
        sizes, widths = [best[1], Bp - best[1]], [best[2], top]
    parts, lens_s, at = [[] for _ in range(D)], [[] for _ in range(D)], {}
    start = 0
    for size, w in zip(sizes, widths):
        b = size // D
        for d in range(D):
            block = np.zeros((b, w), np.uint32)
            for j in range(b):
                i = order[start + d * b + j]
                at[i] = d * (Bp // D) + len(lens_s[d])
                lens_s[d].append(lens[i])
                if i < B:
                    block[j, :lens[i]] = rows[i]
            parts[d].append(block.ravel())
        start += size
    arena = np.stack([np.concatenate(p) for p in parts])
    lens_s = np.concatenate(lens_s).astype(np.int32)
    valid = np.zeros(Bp, bool)
    valid[[at[i] for i in range(B)]] = True
    return (arena, lens_s, valid, B, np.asarray([at[i] for i in range(B)]),
            tuple((s // D, w) for s, w in zip(sizes, widths)))


def _assert_staged(staged, items):
    arena, lens, valid, B, at, groups = _reference(items)
    assert staged.tokens.groups == groups
    for got, ref in zip((staged.tokens.data, staged.lens, staged.valid),
                        (arena, lens, valid)):
        got = np.asarray(got)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(got, ref)
    assert staged.n == B
    np.testing.assert_array_equal(staged.pick(np.arange(len(lens))), at)


def _lognormal(n, median, sigma=1.0):
    return np.maximum(np.rint(median * np.exp(
        sigma * RNG.standard_normal(n))), 1).astype(int)


# (items, groups) in call order, all on one filter
SEQUENCE = [
    (lambda: _ragged([3, 1, 13, 7, 2]), ((8, 16),)),
    (lambda: _ragged([1, 12, 2, 16, 7, 3, 9]), ((8, 16),)),  # shrink, grow
    (lambda: _ragged([5, 10, 1, 1, 2, 2]), ((8, 16),)),  # B falls in it
    (lambda: _ragged([40, 3, 17]), ((4, 64),)),          # another shape
    (lambda: _ragged([2, 30, 64]), ((4, 64),)),
    (lambda: _ragged([9, 1, 4, 14, 2, 6, 11, 8]), ((8, 16),)),  # and back
    (lambda: RNG.integers(0, 2**32, (5, 15), dtype=np.uint64)
     .astype(np.uint32), ((8, 16),)),                    # dense 2-D
    (lambda: _ragged([1, 2, 3]), ((4, 4),)),
    (lambda: _ragged([4, 1, 2, 1]), ((4, 4),)),
    (lambda: np.arange(24, dtype=np.int64).reshape(3, 8) + (1 << 32) - 12,
     ((4, 8),)),                                         # dense, cast
    (lambda: [[7, 1 << 33, 5], np.uint32(9), np.arange(6, dtype=np.int16)
              - 3], ((4, 8),)),                          # rows to be cast
    (lambda: _ragged([2, 1, 7, 2]), ((4, 8),)),
    # past 32 rows the length-sorted rows may be cut in two
    (lambda: _ragged([3] * 50 + [100] * 20), ((96, 4), (32, 128))),
    (lambda: _ragged(list(_lognormal(100, 6)) + [0, 1, 32, 33, 64, 65]),
     None),                                              # lengths 0 and 1
    (lambda: _ragged([0] * 3 + list(RNG.integers(5, 9, 120))),
     ((128, 8),)),                                       # one width: collapse
    (lambda: RNG.integers(0, 2**32, (120, 9), dtype=np.uint64)
     .astype(np.uint32), ((128, 16),)),                  # dense, collapsed
    (lambda: _ragged([3] * 50 + [100] * 20), ((96, 4), (32, 128))),
    (lambda: _ragged([1] * 40 + [128] * 30), ((96, 1), (32, 128))),
    (lambda: _ragged([50] * 62 + [2] * 2 + [100] * 64), ((64, 64), (64, 128))),
    (lambda: _ragged(_lognormal(300, 20)), None),
    (lambda: _ragged(_lognormal(300, 20)), None),
]


def test_staging_equals_a_fresh_stack_over_a_sequence_of_calls():
    bloom = DeviceShardedBloom(n_items=500, mesh=data_mesh(1))
    shapes = []
    for make, groups in SEQUENCE:
        items = make()
        staged = bloom._stage(items)
        if groups is not None:
            assert staged.tokens.groups == groups
        _assert_staged(staged, items)
        shapes.append(staged.tokens.groups)
    allocs = 1 + sum(a != b for a, b in zip(shapes, shapes[1:]))
    assert bloom.stats["stage_allocs"] == allocs
    assert bloom.stats["stage_reuses"] == len(SEQUENCE) - allocs
    assert bloom.stats["stage_groups"] == sum(map(len, shapes))


class _AlignedNumpy:
    """numpy, but `zeros` returns 64-byte-aligned memory: host arrays the
    CPU client hands to the device without a copy."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def zeros(shape, dtype=float):
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        raw = np.zeros(n + 64, np.uint8)
        off = -raw.ctypes.data % 64
        return raw[off:off + n].view(dtype).reshape(shape)


@pytest.mark.parametrize("memory", ["as_allocated", "aligned"])
def test_staged_arrays_survive_later_rewrites(memory, monkeypatch):
    """Every call of one shape of groups rewrites the same host arena; the
    device arrays of earlier calls, among them those the routed add keeps
    for an overflow replay, keep the bytes they were staged with -- also
    where the runtime could alias the arena's memory. Small calls stage one
    bucket, calls past 32 rows two groups."""
    if memory == "aligned":
        monkeypatch.setattr(distributed, "np", _AlignedNumpy())
    bloom = DeviceShardedBloom(n_items=500, mesh=data_mesh(1))
    kept = []
    for _ in range(12):  # one bucket: (8, 16)
        items = _ragged(list(RNG.integers(1, 17, size=RNG.integers(4, 8)))
                        + [16])
        kept.append((bloom._stage(items), items))
    for _ in range(6):  # one shape of two groups: (96, 4), (32, 16)
        items = _ragged([3] * 60 + list(RNG.integers(9, 16, 7)) + [16])
        kept.append((bloom._stage(items), items))
    for staged, items in kept:
        _assert_staged(staged, items)
    assert bloom.stats["stage_reuses"] == 11 + 5

    waves = [_ragged(RNG.integers(1, 17, size=6)) for _ in range(3)] + [
        _ragged(list(RNG.integers(1, 5, 60)) + [16] * 8) for _ in range(2)]
    for w in waves:
        bloom.add_batch(w)
    assert len(bloom._pending) == len(waves)  # flags not yet read
    for (_, staged), w in zip(bloom._pending, waves):
        _assert_staged(staged, w)


@pytest.mark.parametrize("transport", ["routed", "all_gather"])
def test_verdicts_over_waves_match_staging_afresh_and_the_host_filter(
        transport):
    """Waves of varying shape through one filter give the verdicts and bits
    of a twin whose arena is dropped before every call, and the decisions
    of the single-device host filter. Waves past 32 rows stage two
    length-sorted groups, smaller ones one bucket."""
    bloom = DeviceShardedBloom(n_items=2000, fp_rate=1e-3, mesh=data_mesh(1),
                               probe_transport=transport)
    twin = DeviceShardedBloom(n_items=2000, fp_rate=1e-3, mesh=data_mesh(1),
                              probe_transport=transport)
    # the twin shares the filter's hasher and compiled surfaces: the two
    # differ in their staging alone
    twin.sharded = bloom.sharded
    for name in ("_add_dev", "_contains_dev", "_admit_dev", "_add_rt",
                 "_contains_rt", "_admit_rt"):
        setattr(twin, name, getattr(bloom, name))
    host = BloomFilter(n_items=2000, fp_rate=1e-3)
    seen = []
    for w in range(8):
        n = int(RNG.integers(20, 40)) if w % 2 else int(RNG.integers(60, 90))
        fresh = _ragged(RNG.integers(1, 24 if w % 3 else 60, size=n))
        wave = fresh + [seen[j] for j in RNG.integers(0, len(seen), n // 3)] \
            if seen else fresh
        seen.extend(fresh)
        twin._buf = None
        if w % 4 == 3:  # an add and a membership read between admissions
            bloom.add_batch(wave)
            twin.add_batch(wave)
            host.add_batch(wave)
            twin._buf = None
            got, ref = bloom.contains_batch(wave), twin.contains_batch(wave)
            np.testing.assert_array_equal(got, host.contains_batch(wave))
        else:
            got = bloom.check_and_add_batch(wave)
            ref = twin.check_and_add_batch(wave)
            want = ~host.contains_batch(wave)
            host.add_batch(wave)
            np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(np.asarray(bloom.bits),
                                  np.asarray(twin.bits))
    assert bloom.stats["stage_reuses"] > 0
    assert twin.stats["stage_reuses"] == 0
    assert bloom.stats["stage_groups"] > bloom.stats["calls"]
