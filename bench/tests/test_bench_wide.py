"""Bit positions past 2^31 and 2^32, on the CPU: the (owner, local) split,
the preloaded state's definition over 64-bit positions and its device
generator at wide starts, and a cell run on a real four-device mesh."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import reference, run, systems
from bench.tests import tiny

SEED = 2**31 + 7


def _cfg():
    return run.load_cell("dedup_docs").cfg


def _old_preloaded(cfg, seed, pos):
    """The definition before 64-bit positions: F(F(i xor s1) + s2) < thr,
    with i taken mod 2^32."""
    s1, s2, thr = reference.preload_params(cfg, seed)
    h = reference.fmix32(pos.astype(np.uint32) ^ np.uint32(s1))
    with np.errstate(over="ignore"):
        h = reference.fmix32(h + np.uint32(s2))
    return h < np.uint32(thr)


@pytest.mark.parametrize("m_local", [1_437_758_756, 1_797_198_446, 2**31 - 1])
def test_split_is_integer_arithmetic(m_local):
    rng = np.random.default_rng(m_local)
    pos = np.concatenate([
        rng.integers(0, 2**40, 4096, dtype=np.int64),
        np.array([0, 1, m_local - 1, m_local, m_local + 1, 2**31 - 1, 2**31,
                  2**32 - 1, 2**32, 2**32 + 1, 2**40 - 1], np.int64)])
    owner, local = systems.split(pos, m_local)
    assert owner.dtype == local.dtype == np.int32
    for p, o, lo in zip(pos.tolist(), owner.tolist(), local.tolist()):
        assert (o, lo) == divmod(p, m_local)


@pytest.mark.parametrize("start", [0, 2**31 - 8, 2**32 - 8, 5 * 2**32 + 17])
def test_preload_block_is_the_reference_at_wide_starts(start):
    cfg, n = _cfg(), 1 << 16
    got = np.asarray(systems.preload_block(cfg, SEED, start, n))
    pos = start + np.arange(n, dtype=np.int64)
    assert got.dtype == np.uint8
    assert np.array_equal(got != 0, reference.preloaded(cfg, SEED, pos))
    p = 1 - np.exp(-cfg["k"] * cfg["preload_items"] / cfg["m_bits"])
    assert abs(got.mean() - p) < 0.02


def test_the_definition_is_unchanged_below_2_32():
    """hi = 0 leaves today's formula; past 2^32 a position is not read as
    its wrapped low word."""
    cfg = _cfg()
    pos = np.concatenate([np.arange(1 << 20, dtype=np.int64),
                          2**31 + np.arange(-(1 << 18), 1 << 18),
                          2**32 - np.arange(1, 1 << 12)])
    assert np.array_equal(reference.preloaded(cfg, SEED, pos),
                          _old_preloaded(cfg, SEED, pos))
    wide = pos[:1 << 16] + 2**32
    assert (reference.preloaded(cfg, SEED, wide)
            != _old_preloaded(cfg, SEED, wide)).mean() > 0.2


def test_reference_bloom_holds_bits_past_2_32():
    m = 7_188_793_783
    f = reference.Bloom(m, 9, 45312)
    rows = [np.arange(n, dtype=np.uint32) for n in (1, 5, 300)]
    p = f.probes(rows)
    assert (p >= 2**32).any()
    assert f.check_and_add(rows).all()
    assert not f.check_and_add(rows).any()
    assert f.bits_at(p).all()
    assert len(f.held) == len(np.unique(p))
    assert not f.bits_at(np.unique(p) + 1).all()


def test_dedup_docs_on_four_devices():
    """The tiny `dedup_docs` cell through `run.run_cell` on four CPU
    devices: the routed exchange is real, the preload is made shard by
    shard and `bits_at` reads every shard."""
    code = textwrap.dedent("""
        import numpy as np
        import jax
        from repro.hash import HashPlan, hasher as hasher_mod
        from bench import reference, run, systems
        from bench.tests import tiny

        assert jax.device_count() == 4
        hasher_mod.default_plan = lambda: HashPlan(backend="interpret")
        cell = run.load_cell("dedup_docs")
        cell.chips = 4
        cell = tiny.shrink(cell)
        seed, owners, made = 2**33 + 19, set(), []

        class Seen(systems.BloomDedup):
            def bits_at(self, pos):
                owners.update(systems.split(pos, self.m_local)[0].tolist())
                return super().bits_at(pos)

        r = run.run_cell(cell, seed, 0.5, False,
                         build=lambda *a: made.append(Seen(*a)) or made[-1])
        assert r["correct"] is True, r["checks"]
        assert r["checks"]["probe_bits_missing"]["value"] == 0
        assert r["checks"]["repeat_admitted"]["value"] == 0
        assert owners == {0, 1, 2, 3}, owners
        m = cell.cfg["m_bits"]
        sysobj = made[0]
        assert len(sysobj.filt.bits.addressable_shards) == 4
        every = np.asarray(sysobj.filt.bits)
        pos = np.arange(m)
        assert np.array_equal(sysobj.bits_at(pos), every[:m] != 0)
        fresh = systems.build(cell.cfg, 4, seed)
        bits = np.asarray(fresh.filt.bits)
        assert fresh.m_local * 4 == len(bits) > m
        assert not bits[m:].any()
        assert np.array_equal(bits[:m] != 0,
                              reference.preloaded(cell.cfg, seed, pos))
        assert np.array_equal(fresh.bits_at(pos), bits[:m] != 0)
        # each shard's own bound: shard 1 cut inside, shards 2 and 3 empty
        half = dict(cell.cfg, m_bits=m // 2 + 5)
        systems.preload(fresh.filt, half, seed)
        bits = np.asarray(fresh.filt.bits)
        assert not bits[half["m_bits"]:].any()
        assert np.array_equal(bits[:half["m_bits"]] != 0, reference.preloaded(
            half, seed, np.arange(half["m_bits"])))
        print("ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(run.ROOT, "src"),
                                           run.ROOT]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=run.ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")


def test_tiny_preload_reads_the_reference_on_one_device():
    cell = tiny.cell("dedup_paragraphs")
    sysobj = systems.build(cell.cfg, 1, 2**40 + 3)
    pos = np.random.default_rng(5).integers(0, cell.cfg["m_bits"], (64, 9))
    got = sysobj.bits_at(pos)
    assert got.shape == pos.shape
    assert np.array_equal(got, reference.preloaded(cell.cfg, 2**40 + 3, pos))
    with pytest.raises(ValueError):
        sysobj.bits_at(np.array([cell.cfg["m_bits"]]))
