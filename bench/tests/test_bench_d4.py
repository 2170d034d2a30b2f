"""The four-chip cell `dedup_docs_d4`: its files resolve by name, its
configuration is the program's own sizing past 2^32, its tiny size runs
correct through `run.run_cell` on four CPU devices, and its two exchange
readers compute their formulas and read nothing where there is nothing."""
import json
import math
import os
import subprocess
import sys
import textwrap
import types

import pytest

from bench import run, trace_reduce
from bench.tests import tiny

CELL = "dedup_docs_d4"


def test_cell_resolves_its_config_mix_and_readers():
    cell = run.load_cell(CELL)
    assert cell.chips == 4
    assert cell.cfg["name"] == "corpus_dedup_bloom_d4"
    assert cell.mix["wave_items"] == 16384
    assert {m["name"] for m in cell.end_to_end} == {"dedup_gbytes_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share.dedup", "multihash_roofline", "wave_ms_p50.dedup",
        "exchange_ms_per_wave.dedup", "exchange_ici_roofline"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(run.reader(cell.bench_dir, m["name"]))


def test_config_is_the_program_sizing_past_2_32():
    """m and k as the filter sizes them, 1.67 x 2^32 bits, a shard below
    the int32 local index on four chips and past it on one; the preload
    half full; the mix is docs' with four chips' items a wave."""
    cfg = run.load_cell(CELL).cfg
    m, k = tiny.bloom_size(cfg["n_items"], cfg["fp_rate"])
    assert (m, k) == (cfg["m_bits"], cfg["k"]) == (7_188_793_783, 9)
    assert 2**32 < m and -(-m // 4) == 1_797_198_446 < 2**31
    fill = 1 - math.exp(-k * cfg["preload_items"] / m)
    assert fill == pytest.approx(0.2687, abs=1e-4)
    one = run.load_cell("dedup_docs")
    for key in ("guarantees", "limits", "family", "transport", "seed"):
        assert cfg[key] == one.cfg[key], key
    mix, docs = run.load_cell(CELL).mix, one.mix
    assert {k: v for k, v in mix.items() if k not in (
        "assumed", "wave_items", "min_wave_ms")} == {
        k: v for k, v in docs.items() if k not in (
            "assumed", "wave_items", "min_wave_ms")}
    assert (mix["wave_items"], mix["min_wave_ms"]) == (
        4 * docs["wave_items"], 4 * docs["min_wave_ms"])


def test_tiny_cell_on_four_devices():
    """The tiny cell through `run.run_cell` on four CPU devices: correct,
    every shard read back, and the routed exchange counted."""
    code = textwrap.dedent("""
        import jax
        from repro.hash import HashPlan, hasher as hasher_mod
        from bench import run, systems
        from bench.tests import tiny

        assert jax.device_count() == 4
        hasher_mod.default_plan = lambda: HashPlan(backend="interpret")
        cell = tiny.cell("dedup_docs_d4")
        owners, made = set(), []

        class Seen(systems.BloomDedup):
            def bits_at(self, pos):
                owners.update(systems.split(pos, self.m_local)[0].tolist())
                return super().bits_at(pos)

        r = run.run_cell(cell, 2**35 + 3, 0.5, False,
                         build=lambda *a: made.append(Seen(*a)) or made[-1])
        assert r["correct"] is True, r["checks"]
        assert r["device"]["count"] == 4
        assert owners == {0, 1, 2, 3}, owners
        st = made[0].filt.stats
        assert made[0].filt.n_shards == 4
        assert st["exchange_slots"] > 0 and st["overflow_fallbacks"] == 0
        assert st["exchange_bytes"] == 8 * st["exchange_slots"]
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


def _run(op_s, waves=2):
    trace = trace_reduce.Reduction(window_s=1.0, n_devices=4, busy_s=0.5,
                                   op_s=op_s, idle_gaps=[])
    cell = types.SimpleNamespace(chips=4, cfg={"k": 9})
    return run.Run(cell, 0, 1.0, 1.0,
                   [run.Wave(16384, 10**7, 16106, 0.0, 0.3)] * waves,
                   trace=trace, peaks={"ici_bits_per_s": 1.6e12})


def test_exchange_readers_compute_their_formulas():
    ms = run.reader(f"{run.ROOT}/bench", "exchange_ms_per_wave.dedup")
    roof = run.reader(f"{run.ROOT}/bench", "exchange_ici_roofline")
    rec = _run({"%all_to_all.3 s32[4,11536,2] all-to-all": 0.004,
                "%psum.7 s32[16384] all-reduce": 0.001,
                "%all-gather-start.1 s32[64] all-gather-start": 0.0005,
                "%all-gather-done.1 s32[64] all-gather-done": 0.0005,
                "%multihash.1 u32[4096,9,2] custom-call": 2.0,
                "%fusion.47 s32[36864] fusion": 0.5})
    assert ms(rec) == pytest.approx(0.006 / 2 * 1e3)
    wave = 16384 * 9 * 3 / 4 * 4 + 16384 * 4  # bytes over all four chips
    per_chip = 2 * wave / 4
    assert roof(rec) == pytest.approx(100 * per_chip / 2e11 / 0.006)


def test_exchange_readers_read_nothing_without_a_collective():
    ms = run.reader(f"{run.ROOT}/bench", "exchange_ms_per_wave.dedup")
    roof = run.reader(f"{run.ROOT}/bench", "exchange_ici_roofline")
    rec = _run({"%multihash.1 u32[4096,9,2] custom-call": 2.0,
                "%copy.44 u8[1437758756] copy": 0.5})
    assert ms(rec) is None and roof(rec) is None
    rec.trace = None
    assert ms(rec) is None and roof(rec) is None
    rec = _run({"%psum.7 s32[16384] all-reduce": 0.001}, waves=0)
    assert ms(rec) is None and roof(rec) is None


def test_benchmark_entries_of_the_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = {w["name"]: w for w in bench["workloads"]}
    assert wl[CELL]["chips"] == 4 and wl[CELL]["traffic"] == "docs_d4"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("exchange_")}
    assert set(new) == {"exchange_ms_per_wave.dedup", "exchange_ici_roofline"}
    for m in new.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "dedup_gbytes_per_s"
