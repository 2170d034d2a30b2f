"""`DeviceShardedBloom`'s own instruments on a mesh of one, kernels in
interpret mode: the padding and staging-group counters in `stats` are
exact on every in-graph path, and one admission call under the profiler
writes the documented spans, nested as documented, with its metadata."""
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.hash import DeviceShardedBloom, HashPlan, distributed
from repro.hash import hasher as hasher_mod
from repro.hash.distributed import _bucket_shape
from repro.parallel.sharding import data_mesh

RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x7ACE)))

# every span of one routed admission call: name -> its parent
NESTING = {
    "bloom.check_and_add_batch": None,
    "bloom.stage": "bloom.check_and_add_batch",
    "bloom.stage.stack": "bloom.stage",
    "bloom.stage.pad": "bloom.stage",
    "bloom.stage.to_device": "bloom.stage",
    "bloom.launch": "bloom.check_and_add_batch",
    "bloom.readback": "bloom.check_and_add_batch",
}


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setattr(hasher_mod, "default_plan",
                        lambda: HashPlan(backend="interpret"))


def _ragged(lengths):
    return [RNG.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
            for n in lengths]


def _bloom(transport="routed"):
    return DeviceShardedBloom(n_items=500, mesh=data_mesh(1),
                              probe_transport=transport)


def _staged(lengths):
    Bp, Np = _bucket_shape(len(lengths), max(lengths), 1)
    return Bp * Np


# a call past 32 rows: 128 staged rows, length-sorted, cut into groups of
# 96 and 32 rows, as wide as 4 and 128 tokens
LADDER = [3] * 50 + [100] * 20
LADDER_SLOTS = 96 * 4 + 32 * 128


def _spans(tmp_path, fn) -> list:
    """(name, start_ns, end_ns, stats) of every `bloom.*` host event that
    `fn()` writes under the profiler, in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}) for e in line.events
                           if e.name.startswith("bloom."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("transport", ["routed", "all_gather"])
def test_counters_are_exact_on_every_in_graph_path(transport):
    bloom = _bloom(transport)
    k, routed = bloom.k, transport == "routed"
    assert bloom.stats == {"overflow_fallbacks": 0, "calls": 0, "items": 0,
                           "real_tokens": 0, "staged_tokens": 0,
                           "stage_groups": 0,
                           "stage_reuses": 0, "stage_allocs": 0,
                           "exchange_slots": 0, "exchange_bytes": 0}
    # three ragged batches and a dense one, all in the (8, 16) bucket
    a, b, c = [3, 1, 13, 7, 2], [5, 9, 4, 1, 1, 2], [16, 2, 6, 6, 1, 11, 3]
    bloom.check_and_add_batch(_ragged(a))
    # one device: a routed bucket's capacity clamps to its 8 x k probes
    assert bloom.stats == {"overflow_fallbacks": 0, "calls": 1, "items": 5,
                           "real_tokens": sum(a),
                           "staged_tokens": _staged(a),
                           "stage_groups": 1,
                           "stage_reuses": 0, "stage_allocs": 1,
                           "exchange_slots": 8 * k * routed,
                           "exchange_bytes": 8 * k * 8}
    assert _staged(a) == 8 * 16  # 5 rows -> 8, width 13 -> 16
    bloom.add_batch(_ragged(b))
    bloom.contains_batch(_ragged(c))
    assert bloom.stats["calls"] == 3
    assert bloom.stats["items"] == 5 + 6 + 7
    assert bloom.stats["real_tokens"] == sum(a) + sum(b) + sum(c)
    assert bloom.stats["staged_tokens"] == (_staged(a) + _staged(b)
                                            + _staged(c))
    dense = np.arange(75, dtype=np.uint32).reshape(5, 15)
    bloom.contains_batch(dense)  # a dense batch: every row is N long
    assert bloom.stats["real_tokens"] == sum(a) + sum(b) + sum(c) + 75
    assert bloom.stats["staged_tokens"] == (_staged(a) + _staged(b)
                                            + _staged(c) + _staged([15] * 5))
    # one bucket so far: every call after the first reused its buffer
    assert (bloom.stats["stage_reuses"], bloom.stats["stage_allocs"]) == (3, 1)
    bloom.check_and_add_batch(_ragged([40, 3]))  # the (2, 64) bucket
    bloom.add_batch(_ragged([33]))               # (1, 64): another shape
    bloom.contains_batch(_ragged([2, 64]))       # (2, 64) again
    bloom.check_and_add_batch(_ragged([9, 1]))   # (2, 16)
    bloom.add_batch(_ragged([12, 10]))           # (2, 16): reused
    assert (bloom.stats["stage_reuses"], bloom.stats["stage_allocs"]) == (4, 5)
    assert bloom.stats["calls"] == 9
    rows = 4 * 8 + 2 + 1 + 2 + 2 + 2  # staged rows of the nine calls
    assert bloom.stats["exchange_slots"] == rows * k * routed
    assert bloom.stats["exchange_bytes"] == rows * k * 8
    assert bloom.stats["stage_groups"] == 9  # one bucket each
    staged = bloom.stats["staged_tokens"]
    bloom.check_and_add_batch(_ragged(LADDER))   # two groups
    assert bloom.stats["stage_groups"] == 9 + 2
    assert bloom.stats["staged_tokens"] == staged + LADDER_SLOTS
    assert bloom.stats["real_tokens"] == (sum(a) + sum(b) + sum(c) + 75
                                          + 40 + 3 + 33 + 66 + 10 + 22
                                          + sum(LADDER))
    assert bloom.stats["exchange_bytes"] == (rows + 128) * k * 8


def test_admission_call_writes_every_span_nested(tmp_path):
    bloom = _bloom()
    lengths = [6, 1, 9, 4, 3]
    for n in (lengths, LADDER):  # compile outside the trace
        bloom.check_and_add_batch(_ragged(n))
    spans = _spans(tmp_path, lambda: [bloom.check_and_add_batch(
        _ragged(n)) for n in (lengths, LADDER)])
    outer = [e for e in spans if e[0] == "bloom.check_and_add_batch"]
    assert [e[3] for e in outer] == [
        {"call": 3, "items": 5, "width": 16, "groups": 1, "shards": 1,
         "m_bits": bloom.m},
        {"call": 4, "items": 70, "width": 128, "groups": 2, "shards": 1,
         "m_bits": bloom.m}]
    for call in outer:
        inside = [e for e in spans if _within(e, call)]
        assert sorted(e[0] for e in inside) == sorted(NESTING)
        named = {e[0]: e for e in inside}
        for name, parent in NESTING.items():
            if parent is not None:
                assert _within(named[name], named[parent]), name
        # stage, launch and readback in that order, none overlapping
        order = [named[n] for n in ("bloom.stage", "bloom.launch",
                                    "bloom.readback")]
        assert all(x[2] <= y[1] for x, y in zip(order, order[1:]))
        stage = [named[n] for n in ("bloom.stage.stack", "bloom.stage.pad",
                                    "bloom.stage.to_device")]
        assert all(x[2] <= y[1] for x, y in zip(stage, stage[1:]))
    assert not [e for e in spans if e[0] == "bloom.overflow_replay"]


def test_add_and_contains_get_only_the_stage_spans(tmp_path):
    bloom = _bloom()
    items = _ragged([5, 2, 7])
    bloom.add_batch(items)
    bloom.contains_batch(items)
    spans = _spans(tmp_path, lambda: (bloom.add_batch(items),
                                      bloom.contains_batch(items)))
    names = sorted({e[0] for e in spans})
    assert names == ["bloom.stage", "bloom.stage.pad", "bloom.stage.stack",
                     "bloom.stage.to_device"]
    assert sum(e[0] == "bloom.stage" for e in spans) == 2


def test_routed_overflow_writes_the_replay_span(tmp_path, monkeypatch):
    # one probe a routed bucket: every routed launch overflows
    monkeypatch.setattr(distributed, "_CAPACITY_FACTOR", 1e-9)
    monkeypatch.setattr(distributed, "_CAPACITY_SLACK", 0)
    bloom = _bloom()
    warm = _ragged([3, 4, 5])
    bloom.add_batch(warm)
    bloom.check_and_add_batch(warm)  # compile both paths outside the trace
    items, other = _ragged([6, 2, 8]), _ragged([4, 4, 7, 1])  # one bucket

    def work():
        bloom.add_batch(items)           # its flag settles in the next call
        return bloom.check_and_add_batch(other)

    spans = _spans(tmp_path, work)
    call = next(e for e in spans if e[0] == "bloom.check_and_add_batch")
    replays = [e for e in spans if e[0] == "bloom.overflow_replay"]
    assert len(replays) == 2  # `_settle`'s replay, then the admission's own
    assert all(_within(r, call) for r in replays)
    readback = next(e for e in spans if e[0] == "bloom.readback")
    assert readback[2] <= replays[1][1]
    assert bloom.stats["overflow_fallbacks"] == 4
    # four routed launches, each of one slot a bucket (the capacity's
    # floor), and each replayed through all_gather: (3 or 4 rows -> 4) x k
    # pairs of 8 bytes
    k = bloom.k
    assert bloom.stats["exchange_slots"] == 4
    assert bloom.stats["exchange_bytes"] == 4 * 8 + 4 * (4 * k * 8)
