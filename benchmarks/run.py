"""Benchmark orchestrator -- one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see common.row) and persists
the machine-readable twin to BENCH_kernels.json (name, us/call, bytes/s,
cycles/byte-equivalent) so the perf trajectory has a committed baseline.

  table2  -- Multilinear vs 2-by-2 vs HM (paper Table 2)
  table3  -- vs Rabin-Karp / SAX (paper Table 3)
  table4  -- vs NH (paper Table 4)
  gf      -- GF(2^32) carry-less variants (paper §5.4)
  wordsize-- word-size/Stinson trade-off (paper §3.2/§5.5, Figs 1-3)
  kernels -- Pallas kernel VMEM/roofline model + interpret sanity
  multihash -- fused K-function engine vs seed host Bloom loop
  hasher  -- Hasher object API vs legacy free functions (overhead ~0)
  tree    -- tree fingerprints (hash.tree): leaf-launch throughput, fold
            tail, digest rate vs the serial stream_digest_host baseline
  distributed -- shard_map scale-out engine vs single-device (live devices)
  quality -- per-row-keyed family evaluation rate of the hash-quality
            battery (repro.quality)

Flags: --fast (CI smoke sizes), --json PATH (default BENCH_kernels.json),
--only mod1,mod2 (subset by name above).
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

from . import common


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small sizes / few repeats (CI smoke)")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' to disable; "
                         "defaults to BENCH_kernels.json for FULL runs only, "
                         "so subset runs never clobber the committed baseline)")
    ap.add_argument("--only", default="",
                    help="comma-separated module subset (e.g. kernels,multihash)")
    args = ap.parse_args(argv)
    common.FAST = bool(args.fast)
    common.ROWS.clear()
    common.JSON_ROWS.clear()

    from . import (distributed_bench, gf_variants, hasher_bench,
                   kernels_bench, multihash_bench, quality_bench,
                   table2_multilinear, table3_common, table4_nh, tree_bench,
                   wordsize)

    modules = {
        "table2": table2_multilinear,
        "table3": table3_common,
        "table4": table4_nh,
        "gf": gf_variants,
        "wordsize": wordsize,
        "kernels": kernels_bench,
        "multihash": multihash_bench,
        "hasher": hasher_bench,
        "tree": tree_bench,
        "distributed": distributed_bench,
        "quality": quality_bench,
    }
    only = [s for s in args.only.split(",") if s]
    unknown = [s for s in only if s not in modules]
    if unknown:
        ap.error(f"unknown --only modules {unknown}; have {sorted(modules)}")
    selected = [modules[s] for s in only] if only else list(modules.values())
    json_path = args.json
    if json_path is None:
        # default committed-baseline path ONLY for full, full-size runs:
        # subset and --fast smoke runs must not clobber the real baseline
        json_path = "" if (only or args.fast) else "BENCH_kernels.json"

    print("name,us_per_call,derived")
    failures = 0
    for mod in selected:
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        if json_path:
            print(f"# {failures} module(s) failed -- NOT writing partial {json_path}")
        sys.exit(1)
    if json_path:
        common.write_json(json_path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
