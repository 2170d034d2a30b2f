"""`DeviceShardedBloom._stage` writes into one kept bucket buffer: what it
hands the device must equal, byte for byte, a fresh zero-padded stack of
the same items, over any sequence of calls on one filter -- rows that
shrink and grow, batches smaller than the bucket, bucket-shape changes and
dense input -- and no array it handed out may change when a later call
rewrites the buffer."""
import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom
from repro.hash import distributed
from repro.hash.distributed import _bucket_shape
from repro.parallel.sharding import data_mesh

RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x57A6E)))


def _ragged(lengths):
    return [RNG.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
            for n in lengths]


def _reference(items, D=1):
    """The staging that allocates afresh on every call: every row cast and
    stacked into a zeroed (B, N) array, then copied into a zeroed bucket."""
    if isinstance(items, (list, tuple)):
        rows = [np.atleast_1d(np.asarray(r)).astype(np.uint32) for r in items]
        lens = np.asarray([len(r) for r in rows], np.int64)
        toks = np.zeros((len(rows), lens.max()), np.uint32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
    else:
        toks = np.atleast_2d(np.asarray(items)).astype(np.uint32)
        lens = np.full(toks.shape[0], toks.shape[1], np.int64)
    B, N = toks.shape
    Bp, Np = _bucket_shape(B, N, D)
    toks_p = np.zeros((Bp, Np), np.uint32)
    toks_p[:B, :N] = toks
    lens_p = np.zeros(Bp, np.int32)
    lens_p[:B] = lens
    valid = np.zeros(Bp, bool)
    valid[:B] = True
    return toks_p, lens_p, valid, B


def _assert_staged(staged, items):
    want = _reference(items)
    for got, ref in zip(staged[:3], want[:3]):
        got = np.asarray(got)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(got, ref)
    assert staged[3] == want[3]


# (items, bucket) in call order, all on one filter
SEQUENCE = [
    (lambda: _ragged([3, 1, 13, 7, 2]), (8, 16)),
    (lambda: _ragged([1, 12, 2, 16, 7, 3, 9]), (8, 16)),  # shrink and grow
    (lambda: _ragged([5, 10, 1, 1, 2, 2]), (8, 16)),  # B falls in the bucket
    (lambda: _ragged([40, 3, 17]), (4, 64)),          # another shape
    (lambda: _ragged([2, 30, 64]), (4, 64)),
    (lambda: _ragged([9, 1, 4, 14, 2, 6, 11, 8]), (8, 16)),  # and back
    (lambda: RNG.integers(0, 2**32, (5, 15), dtype=np.uint64)
     .astype(np.uint32), (8, 16)),                    # dense 2-D
    (lambda: _ragged([1, 2, 3]), (4, 4)),
    (lambda: _ragged([4, 1, 2, 1]), (4, 4)),
    (lambda: np.arange(24, dtype=np.int64).reshape(3, 8) + (1 << 32) - 12,
     (4, 8)),                                         # dense, cast in place
    (lambda: [[7, 1 << 33, 5], np.uint32(9), np.arange(6, dtype=np.int16)
              - 3], (4, 8)),                          # rows to be cast
    (lambda: _ragged([2, 1, 7, 2]), (4, 8)),
]


def test_staging_equals_a_fresh_stack_over_a_sequence_of_calls():
    bloom = DeviceShardedBloom(n_items=500, mesh=data_mesh(1))
    shapes = []
    for make, bucket in SEQUENCE:
        items = make()
        staged = bloom._stage(items)
        assert staged[0].shape == bucket
        _assert_staged(staged, items)
        shapes.append(bucket)
    allocs = 1 + sum(a != b for a, b in zip(shapes, shapes[1:]))
    assert bloom.stats["stage_allocs"] == allocs
    assert bloom.stats["stage_reuses"] == len(SEQUENCE) - allocs


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
    """Every call of one bucket rewrites the same host buffer; the device
    arrays of earlier calls, among them those the routed add keeps for an
    overflow replay, keep the bytes they were staged with -- also where the
    runtime could alias the buffer's memory."""
    if memory == "aligned":
        monkeypatch.setattr(distributed, "np", _AlignedNumpy())
    bloom = DeviceShardedBloom(n_items=500, mesh=data_mesh(1))
    kept = []
    for _ in range(12):
        items = _ragged(RNG.integers(1, 17, size=RNG.integers(5, 9)))
        kept.append((bloom._stage(items), items))
    for staged, items in kept:
        _assert_staged(staged, items)
    assert bloom.stats["stage_reuses"] == 11

    waves = [_ragged(RNG.integers(1, 17, size=6)) for _ in range(5)]
    for w in waves:
        bloom.add_batch(w)
    assert len(bloom._pending) == len(waves)  # flags not yet read
    for (_, (toks, lens, valid)), w in zip(bloom._pending, waves):
        _assert_staged((toks, lens, valid, len(w)), w)


@pytest.mark.parametrize("transport", ["routed", "all_gather"])
def test_verdicts_over_waves_match_staging_afresh_and_the_host_filter(
        transport):
    """Waves of varying shape through one filter give the verdicts and bits
    of a twin whose buffer is dropped before every call, and the decisions
    of the single-device host filter."""
    bloom = DeviceShardedBloom(n_items=2000, fp_rate=1e-3, mesh=data_mesh(1),
                               probe_transport=transport)
    twin = DeviceShardedBloom(n_items=2000, fp_rate=1e-3, mesh=data_mesh(1),
                              probe_transport=transport)
    host = BloomFilter(n_items=2000, fp_rate=1e-3)
    seen = []
    for w in range(8):
        n = int(RNG.integers(20, 40))
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
