"""`DeviceShardedBloom` stages a call past 32 rows a device as two
length-sorted row groups, cut at a rung of a fixed halving ladder: its
verdicts and bits must equal, item for item, the one-bucket staging's and
the single-device `BloomFilter`'s, on every transport and through the
overflow replay, on a mesh of one and on four fake devices; its padding on
the benchmark's own length mixes is bounded; it stays one bucket where no
cut saves slots; and its traces stay bounded."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.data.dedup import BloomFilter
from repro.hash import DeviceShardedBloom
from repro.hash import distributed
from repro.hash.distributed import _ladder
from repro.parallel.sharding import data_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.Generator(np.random.Philox(key=np.uint64(0x1ADD3)))
# k = 4 probes: a quarter of the filter far from full, and half the
# compile time of the default k = 9 on the CPU
FP = 0.05

TRANSPORTS = {
    "routed": "routed",
    "all_gather": "all_gather",
    # one probe a routed bucket (the test shrinks the capacity constants):
    # every routed launch overflows, so each call replays through all_gather
    "routed_overflow": "routed",
}


def _rows(lengths):
    return [RNG.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
            for n in lengths]


# the lengths of every wave of `_wave`: 63 rows shorter than 40 tokens
# (among them 0 and 1, and lengths at and beside powers of two), and 63 of
# 513 to 1024 tokens (log-normal, and 513, 1023, 1024)
_FIXED = np.random.default_rng(0x5EED)
SHORT = [0, 1, 2, 3, 7, 8, 9, 15, 16, 17, 31, 32, 33] + _FIXED.integers(
    0, 40, 50).tolist()
LONG = [513, 1023, 1024] + np.clip(np.rint(700 * np.exp(
    0.3 * _FIXED.standard_normal(60))), 513, 1024).astype(int).tolist()


def _wave(last):
    """128 ragged rows, no padding: the `SHORT` rows, one row of 40 tokens
    sent twice, the `LONG` rows; 20 of them repeat the row in the same slot
    of the `last` wave (so every wave has the same lengths). The length
    order puts the twice-sent row at ranks 63 and 64, and the cut after 64
    rows stages the fewest slots, so its copies land in the two groups: 64
    rows 64 wide, then 64 rows 1024 wide. Returns (the rows in slot order, the wave as sent, the positions
    of the two copies in it)."""
    slots = _rows(SHORT) + _rows([40]) * 2 + _rows(LONG)
    if last:
        for j in RNG.choice([j for j in range(128) if j not in (63, 64)], 20,
                            replace=False).tolist():
            slots[j] = last[j]
    perm = RNG.permutation(128)
    return slots, [slots[i] for i in perm], np.flatnonzero(
        (perm == 63) | (perm == 64))


def _share(src, dst) -> None:
    """`dst` hashes with `src`'s hasher through `src`'s compiled surfaces:
    the two filters differ in their staging alone."""
    dst.sharded = src.sharded
    for name in ("_add_dev", "_contains_dev", "_admit_dev", "_add_rt",
                 "_contains_rt", "_admit_rt"):
        setattr(dst, name, getattr(src, name))


def _one_bucket(monkeypatch, fn, *args):
    """`fn(*args)` with no cut to choose: rows in their own order in one
    Bp x Np bucket."""
    with monkeypatch.context() as mp:
        mp.setattr(distributed, "_ladder", lambda Bp, D: ())
        return fn(*args)


@pytest.mark.parametrize("transport", sorted(TRANSPORTS))
def test_grouped_admission_matches_one_bucket_and_the_host_filter(
        transport, monkeypatch):
    tp = TRANSPORTS[transport]
    if transport == "routed_overflow":
        monkeypatch.setattr(distributed, "_CAPACITY_FACTOR", 1e-9)
        monkeypatch.setattr(distributed, "_CAPACITY_SLACK", 0)
    grouped = DeviceShardedBloom(n_items=3000, fp_rate=FP,
                                 mesh=data_mesh(1), probe_transport=tp)
    bucket = DeviceShardedBloom(n_items=3000, fp_rate=FP,
                                mesh=data_mesh(1), probe_transport=tp)
    _share(grouped, bucket)
    host = BloomFilter(n_items=3000, fp_rate=FP)
    slots = None
    for w in range(3):
        slots, wave, twins = _wave(slots)
        if w == 1:  # add, then read back, between admissions
            grouped.add_batch(wave)
            _one_bucket(monkeypatch, bucket.add_batch, wave)
            host.add_batch(wave)
            got = grouped.contains_batch(wave)
            assert got.all()  # no false negatives
            ref = _one_bucket(monkeypatch, bucket.contains_batch, wave)
            np.testing.assert_array_equal(got, host.contains_batch(wave))
        else:
            want = ~host.contains_batch(wave)  # the pre-batch state
            host.add_batch(wave)
            got = grouped.check_and_add_batch(wave)
            ref = _one_bucket(monkeypatch, bucket.check_and_add_batch, wave)
            np.testing.assert_array_equal(got, want)
            # both copies of the row sent twice admit: verdicts are
            # against the pre-batch state, whichever group each is in
            assert got[twins].all()
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(np.asarray(grouped.bits),
                                      np.asarray(bucket.bits))
    # four staged calls of 128 rows: two groups of 64, or one bucket
    assert grouped.stats["stage_groups"] == 4 * 2
    assert bucket.stats["stage_groups"] == 4
    assert grouped.stats["staged_tokens"] < bucket.stats["staged_tokens"]
    overflow = transport == "routed_overflow"
    assert (grouped.stats["overflow_fallbacks"] > 0) == overflow
    assert (grouped.stats["overflow_fallbacks"]
            == bucket.stats["overflow_fallbacks"])


def test_ladder_cuts_halve_the_longer_part_down_to_32_rows_a_device():
    assert _ladder(4096, 1) == (2048, 3072, 3584, 3840, 3968, 4032, 4064)
    assert _ladder(16384, 4) == (8192, 12288, 14336, 15360, 15872, 16128,
                                 16256)
    assert _ladder(64, 1) == (32,)
    assert _ladder(256, 4) == (128,)
    for Bp, D in ((1, 1), (8, 1), (32, 1), (128, 4), (64, 2)):
        assert _ladder(Bp, D) == ()
    for Bp, D in ((4096, 1), (16384, 4), (2048, 8), (64, 1)):
        cuts = _ladder(Bp, D)
        assert all(c % D == 0 and (Bp - c) >= 32 * D for c in cuts)
        assert [Bp - c for c in cuts] == [Bp >> j for j in
                                          range(1, len(cuts) + 1)]


def _mix_waves(mix: str):
    from bench.generate import wave_lengths

    with open(os.path.join(ROOT, "bench", "traffic", f"{mix}.json")) as f:
        lengths = wave_lengths(json.load(f))
    pool = np.arange(int(lengths.max()), dtype=np.uint32)
    return [pool[:n] for n in RNG.permutation(lengths).tolist()]


# each mix's two groups, (rows, width) on one device, and its staged slots
# a real token: one bucket as wide as the longest item stages 24.2, 18.6
# and 48.4
MIX_GROUPS = {
    "docs": (((3840, 2048), (256, 16384)), 4.36),
    "paragraphs": (((3584, 128), (512, 1024)), 4.36),
    "docs_d4": (((3840, 2048), (256, 32768)), 5.86),
}


@pytest.mark.parametrize("mix", ["docs", "paragraphs", "docs_d4"])
def test_ladder_bounds_padding_on_the_benchmark_mixes(mix):
    """A wave of each benchmark mix, staged as the filter stages it: two
    groups, the longest 1/16 of the rows (docs, docs_d4) or 1/8
    (paragraphs) in the second: at most a quarter of the slots of one
    bucket as wide as the longest item. docs_d4 stages on four fake
    devices, in a subprocess."""
    if mix == "docs_d4":
        code = f"""
            import sys
            sys.path[:0] = [{os.path.join(ROOT, "src")!r}, {ROOT!r},
                            {os.path.join(ROOT, "tests")!r}]
            import test_bloom_ladder as t
            t._stage_mix("docs_d4", 4)
            print("ok")
        """
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, env=env,
                             cwd=ROOT, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        assert out.stdout.strip().endswith("ok")
    else:
        _stage_mix(mix, 1)


def _stage_mix(mix: str, D: int) -> None:
    bloom = DeviceShardedBloom(n_items=1000, mesh=data_mesh(D))
    assert bloom.n_shards == D
    items = _mix_waves(mix)
    st = bloom._stage(items)
    groups, pad = MIX_GROUPS[mix]
    assert st.tokens.groups == groups
    s = bloom.stats
    assert s["stage_groups"] == 2 and s["calls"] == 1
    assert s["real_tokens"] == sum(map(len, items))
    assert s["staged_tokens"] / s["real_tokens"] <= pad
    assert s["staged_tokens"] == D * sum(b * w for b, w in st.tokens.groups)
    # each item's staged row holds its tokens
    arena = np.asarray(st.tokens.data)
    R = sum(b for b, _ in st.tokens.groups)
    for i in RNG.integers(0, len(items), 64).tolist():
        d, j = divmod(int(st.rows[i]), R)
        off = 0
        for b, w in st.tokens.groups:
            if j < b:
                row = arena[d, off + j * w:off + (j + 1) * w]
                break
            j, off = j - b, off + b * w
        n = len(items[i])
        np.testing.assert_array_equal(row[:n], items[i])
        assert not row[n:].any()


@pytest.mark.parametrize("case", ["dense", "32_rows", "one_width"])
def test_ladder_collapses_to_one_bucket(case):
    """One group where no cut saves slots: a dense (B, N) batch, 32 rows
    or fewer a device, rows all of one pow2 width."""
    bloom = DeviceShardedBloom(n_items=1000, mesh=data_mesh(1))
    items = {
        "dense": lambda: RNG.integers(0, 2**32, (4096, 12), dtype=np.uint64)
        .astype(np.uint32),
        "32_rows": lambda: _rows(RNG.integers(0, 300, 32)),
        "one_width": lambda: _rows(RNG.integers(33, 65, 500)),
    }[case]()
    st = bloom._stage(items)
    assert len(st.tokens.groups) == 1 and st.rows is None
    assert bloom.stats["stage_groups"] == 1
    Bp, Np = distributed._bucket_shape(
        len(items), max(len(r) for r in items), 1)
    assert st.tokens.groups == ((Bp, Np),)
    assert bloom.stats["staged_tokens"] == Bp * Np


def test_small_batches_collapse_on_four_devices():
    """32 rows a device or fewer stage one bucket on four fake devices too;
    one more row cuts two groups of 128. All admit as the host filter."""
    code = """
        import numpy as np
        from repro.data.dedup import BloomFilter
        from repro.hash import DeviceShardedBloom
        from repro.parallel.sharding import data_mesh
        rng = np.random.default_rng(4)
        rows = lambda n: [rng.integers(0, 2**32, size=int(k), dtype=np.uint64)
                          .astype(np.uint32) for k in rng.integers(1, 90, n)]
        for transport in ("routed",):
            f = DeviceShardedBloom(n_items=2000, fp_rate=0.05,
                                   mesh=data_mesh(4),
                                   probe_transport=transport)
            h = BloomFilter(n_items=2000, fp_rate=0.05)
            for n, groups in ((128, 1), (129, 2), (97, 1), (300, 2)):
                items = rows(n - 5)
                items += items[:5]
                before = f.stats["stage_groups"]
                want = ~h.contains_batch(items)
                got = f.check_and_add_batch(items)
                assert f.stats["stage_groups"] - before == groups, n
                h.add_batch(items)
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(
                    f.contains_batch(items[::-1]),
                    h.contains_batch(items[::-1]))
        print("ok")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")


def test_grouped_admission_on_four_devices():
    """Four fake devices: grouped admission, add and membership, routed
    and through the overflow replay (whose all_gather surfaces then take
    every grouped call), equal the one-bucket staging's verdicts and bits
    and the host filter's decisions."""
    code = """
        import numpy as np
        from repro.data.dedup import BloomFilter
        from repro.hash import DeviceShardedBloom
        from repro.hash import distributed
        from repro.parallel.sharding import data_mesh
        rng = np.random.default_rng(0xD4)
        def rows(lengths):
            return [rng.integers(0, 2**32, size=int(n), dtype=np.uint64)
                    .astype(np.uint32) for n in lengths]
        ladder = distributed._ladder
        one = lambda Bp, D: ()
        # every wave: these lengths, 20 slots repeating the last wave's
        lens = [0, 1, 2, 31, 32, 33, 127, 128, 129] + list(
            np.maximum(np.rint(9 * np.exp(rng.standard_normal(290))),
                       1).astype(int))
        for overflow in (False, True):
            if overflow:  # one probe a routed bucket: every launch replays
                distributed._CAPACITY_FACTOR = 1e-9
                distributed._CAPACITY_SLACK = 0
            g = DeviceShardedBloom(n_items=4000, fp_rate=0.05,
                                   mesh=data_mesh(4))
            b = DeviceShardedBloom(n_items=4000, fp_rate=0.05,
                                   mesh=data_mesh(4))
            b.sharded = g.sharded  # one hasher and one set of compiles
            for name in ("_add_dev", "_contains_dev", "_admit_dev",
                         "_add_rt", "_contains_rt", "_admit_rt"):
                setattr(b, name, getattr(g, name))
            h = BloomFilter(n_items=4000, fp_rate=0.05)
            slots = None
            for w in range(3):
                last, slots = slots, rows(lens)
                if last:
                    for j in rng.choice(len(lens), 20, replace=False):
                        slots[j] = last[j]
                order = rng.permutation(len(slots))
                wave = [slots[i] for i in order]
                if w == 1:
                    g.add_batch(wave)
                    distributed._ladder = one
                    b.add_batch(wave)
                    distributed._ladder = ladder
                    h.add_batch(wave)
                    got = g.contains_batch(wave)
                    distributed._ladder = one
                    ref = b.contains_batch(wave)
                    distributed._ladder = ladder
                    want = h.contains_batch(wave)
                else:
                    want = ~h.contains_batch(wave)
                    h.add_batch(wave)
                    got = g.check_and_add_batch(wave)
                    distributed._ladder = one
                    ref = b.check_and_add_batch(wave)
                    distributed._ladder = ladder
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(got, ref)
                np.testing.assert_array_equal(np.asarray(g.bits),
                                              np.asarray(b.bits))
            assert g.stats["stage_groups"] > g.stats["calls"]
            assert b.stats["stage_groups"] == b.stats["calls"]
            assert (g.stats["overflow_fallbacks"] > 0) == overflow
        print("ok")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")


def test_stationary_waves_trace_the_admission_once():
    """40 waves of one length mix in shuffled orders, as the benchmark
    sends them: one shape of groups, so one trace of the admission."""
    rng = np.random.default_rng(0x40)
    bloom = DeviceShardedBloom(n_items=20000, fp_rate=FP, mesh=data_mesh(1))
    lengths = np.maximum(np.rint(7 * np.exp(
        rng.standard_normal(200))), 1).astype(int)
    for _ in range(40):
        bloom.check_and_add_batch(_rows(rng.permutation(lengths)))
    assert bloom._admit_rt._cache_size() == 1
    assert bloom.stats["stage_groups"] == 40 * 2  # 256 rows, cut in two
    assert bloom.stats["stage_allocs"] == 1


def test_lognormal_waves_trace_once_per_width_tuple():
    """Waves whose longest items vary trace the admission no more often
    than they have distinct tuples of group widths. The hasher's keys are
    grown to the widest group first: their length is an operand shape of
    its own."""
    rng = np.random.default_rng(0x10)
    bloom = DeviceShardedBloom(n_items=20000, fp_rate=FP, mesh=data_mesh(1))
    waves = [_rows(np.maximum(np.rint(5 * np.exp(
        s * rng.standard_normal(150))), 1).astype(int))
        for s in (0.5, 0.8, 1.0, 0.5, 1.2, 0.8, 1.0, 0.5)]
    longest = max(len(r) for w in waves for r in w)
    bloom.sharded.ensure(distributed._bucket_shape(1, longest, 1)[1])
    widths = set()
    for w in waves:
        bloom.check_and_add_batch(w)
        lens = np.zeros(256, np.int32)
        lens[:len(w)] = [len(r) for r in w]
        widths.add(bloom._groups(lens)[0])
    assert bloom._admit_rt._cache_size() <= len(widths)
    assert 1 < len(widths) < len(waves)
