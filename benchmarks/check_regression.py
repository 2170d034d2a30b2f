"""Bench regression gate: compare a fresh run against the committed baseline.

Compares by row NAME intersection, and ONLY between runs with the same
`fast` flag: the committed `BENCH_kernels.json` is a full-size run, and at
`--fast` smoke sizes fixed dispatch overhead dominates, so fast-vs-full
ratios are size artifacts, not regressions. The default therefore re-runs
the engine subset at FULL size (a couple of minutes). Metric per row:
`cycles_per_byte_equiv` when both sides have it, else `us_per_call`.

Two severity tiers:

- the full report stays NON-BLOCKING at --tolerance (CI-runner timing
  noise, cross-machine baselines); pass --strict to turn any flag into a
  nonzero exit;
- --max-regress R is the BLOCKING PR gate for the pinned hot-path rows
  (--gate name prefixes, default: the engine fast paths): a gated row
  blocks when a paired-sample PERMUTATION TEST concludes, at significance
  --alpha, that its timing distribution is slower than R x the baseline's
  -- the gate tests the recorded `samples_us` distributions (baseline
  samples scaled by R, one-sided two-sample permutation test on the log
  samples), so a single noisy best-of ratio can neither sneak a real
  regression through nor block a clean PR. Gated rows WITHOUT samples on
  either side fail closed (regenerate the baseline with a samples-aware
  bench). --runs N interleaves N fresh subset runs for more samples.
  The distributional gate is what lets --max-regress sit at 1.3x on
  compute-bound rows where the old point-ratio gate needed a 2.5x noise
  allowance. BENCH_kernels.json holds CPU timings: the gate guards the
  CPU lane against regressions and says nothing about the chip.

Usage:
  python -m benchmarks.check_regression                   # runs subset itself
  python -m benchmarks.check_regression --fresh f.json    # compare saved run
  python -m benchmarks.check_regression --max-regress 1.3 --runs 2  # PR gate
"""
from __future__ import annotations

import argparse
import itertools
import json
import math
import random
import sys

# modules with throughput rows that exist at both --fast and full sizes
_SMOKE_MODULES = "kernels,multihash,hasher,tree,distributed,gf"

# hot-path rows gated by --max-regress: the COMPUTE-BOUND jit engine fast
# paths whose regression would invalidate the paper-claim trajectory, plus
# the routed-transport admission rows (the default transport's collective
# layout is a headline claim; its ingraph sibling stays advisory).
# Other host-sync/collective-bound rows (distributed/*) and the interpret
# Python-exec rows swing multi-x on shared-core CPU runners and stay in
# the non-blocking report. Prefix match.
_GATE_PREFIXES = ("multihash/kscale/",
                  "multihash/bloom4096x9probe/fused-jnp",
                  "hasher_overhead/",
                  "tree/leaf_hash/",
                  "tree/digest/",
                  "distributed/bloom_admit/B4096/routed/",
                  "gf/engine/B64xN256/gf_multilinear/",
                  "gf/engine/B64xN256/gf_multilinear_hm/")


def perm_pvalue(base_logs: list, fresh_logs: list,
                max_perms: int = 20000) -> float:
    """One-sided two-sample permutation p-value for H1: fresh > base.

    Statistic: mean(fresh) - mean(base) on log-timings (so the test is a
    ratio test, robust to the timing distribution's right skew). Exact
    enumeration of label reassignments when feasible, else a seeded Monte
    Carlo draw of `max_perms` permutations; either way the p-value includes
    the observed labelling (never returns 0 -- the honest lower bound is
    1/trials).
    """
    pooled = list(base_logs) + list(fresh_logs)
    n_f = len(fresh_logs)
    # mean(F) - mean(B) is monotone in sum(F) for a fixed pool: compare sums
    obs = sum(fresh_logs)
    n_total = math.comb(len(pooled), n_f)
    hits = trials = 0
    if n_total <= max_perms:
        for combo in itertools.combinations(pooled, n_f):
            trials += 1
            hits += sum(combo) >= obs - 1e-12
    else:
        rng = random.Random(0xF5EED)
        for _ in range(max_perms):
            trials += 1
            hits += sum(rng.sample(pooled, n_f)) >= obs - 1e-12
        hits += 1  # count the observed labelling itself
        trials += 1
    return hits / trials


def gate_verdict(base_row: dict, fresh_row: dict, max_regress: float,
                 alpha: float) -> tuple:
    """(p_value | None, blocked, why) for one gated row.

    Tests H1 "fresh is slower than max_regress x baseline" by scaling the
    baseline samples by max_regress and asking the permutation test whether
    fresh still looks slower. Missing samples on either side fail closed.
    """
    bs = base_row.get("samples_us")
    fs = fresh_row.get("samples_us")
    if not bs or not fs:
        side = "baseline" if not bs else "fresh run"
        return None, True, f"no samples_us in {side} (gate fails closed)"
    base_logs = [math.log(s * max_regress) for s in bs]
    fresh_logs = [math.log(s) for s in fs]
    p = perm_pvalue(base_logs, fresh_logs)
    if p <= alpha:
        return p, True, f"slower than {max_regress}x baseline (p={p:.4g})"
    return p, False, f"p={p:.3g}"


def load_rows(path: str) -> tuple[dict, bool]:
    with open(path) as f:
        data = json.load(f)
    if data.get("schema") != "bench-v1":
        raise SystemExit(f"{path}: unknown schema {data.get('schema')!r}")
    return {r["name"]: r for r in data["rows"]}, bool(data.get("fast"))


def compare(base: dict, fresh: dict, tolerance: float):
    """Yield (name, metric, base_val, fresh_val, ratio, flagged) rows."""
    for name in sorted(set(base) & set(fresh)):
        b, f = base[name], fresh[name]
        if b.get("cycles_per_byte_equiv") and f.get("cycles_per_byte_equiv"):
            metric = "cycles/B"
            bv, fv = b["cycles_per_byte_equiv"], f["cycles_per_byte_equiv"]
        elif b["us_per_call"] > 0 and f["us_per_call"] > 0:
            metric = "us/call"
            bv, fv = b["us_per_call"], f["us_per_call"]
        else:
            continue
        ratio = fv / bv
        yield name, metric, bv, fv, ratio, ratio > tolerance


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default="BENCH_kernels.json")
    ap.add_argument("--fresh", default=None,
                    help="saved fresh run; omit to run the engine subset "
                         f"({_SMOKE_MODULES}) in-process at full size")
    ap.add_argument("--tolerance", type=float, default=2.5,
                    help="flag rows slower than tolerance x baseline "
                         "(default 2.5: CPU-runner noise band)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any row is flagged (default: report "
                         "only for non-gated rows)")
    ap.add_argument("--max-regress", type=float, default=None,
                    help="BLOCKING gate: exit 1 when the permutation test "
                         "finds any hot-path row (see --gate) significantly "
                         "slower than this ratio x baseline")
    ap.add_argument("--alpha", type=float, default=0.01,
                    help="significance level of the gate's permutation test "
                         "(default 0.01)")
    ap.add_argument("--runs", type=int, default=1,
                    help="interleaved fresh bench runs; their samples_us "
                         "pool for the permutation test (default 1; only "
                         "without --fresh)")
    ap.add_argument("--gate", default=",".join(_GATE_PREFIXES),
                    help="comma-separated row-name prefixes the --max-regress "
                         "gate applies to")
    args = ap.parse_args(argv)

    base, base_fast = load_rows(args.baseline)
    if args.fresh:
        fresh, fresh_fast = load_rows(args.fresh)
    else:
        import tempfile

        from . import run as bench_run

        fresh = {}
        for _ in range(max(1, args.runs)):
            with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
                bench_run.main(["--only", _SMOKE_MODULES, "--json", tmp.name])
                run_rows, fresh_fast = load_rows(tmp.name)
            for name, r in run_rows.items():
                prev = fresh.get(name)
                if prev is None:
                    fresh[name] = r
                    continue
                # pool the timing evidence across interleaved runs:
                # best-of for the point metrics, concatenated samples
                # for the permutation test
                if r["us_per_call"] < prev["us_per_call"]:
                    keep_samples = (prev.get("samples_us", [])
                                    + r.get("samples_us", []))
                    fresh[name] = r
                    prev = r
                else:
                    keep_samples = (prev.get("samples_us", [])
                                    + r.get("samples_us", []))
                if keep_samples:
                    prev["samples_us"] = keep_samples

    gating = args.max_regress is not None
    if base_fast != fresh_fast:
        print(f"# baseline fast={base_fast} vs fresh fast={fresh_fast}: "
              "sizes differ, ratios would be size artifacts -- not comparing")
        # a BLOCKING gate must fail closed: "could not compare" is a gate
        # failure, not a pass (e.g. a fast=true baseline would otherwise
        # silently disarm the PR gate forever)
        return 1 if gating else 0
    rows = list(compare(base, fresh, args.tolerance))
    if not rows:
        print("# no comparable rows between baseline and fresh run"
              + (" -- BLOCKING (gate has nothing to check)" if gating else ""))
        return 1 if gating else 0
    gate_prefixes = tuple(p for p in args.gate.split(",") if p)
    gated = lambda name: gating and name.startswith(gate_prefixes)  # noqa: E731
    if gating:
        # fail closed PER PREFIX: a partial bench-row rename must not
        # silently narrow the gate's coverage
        uncovered = [p for p in gate_prefixes
                     if not any(r[0].startswith(p) for r in rows)]
        if uncovered:
            print(f"# BLOCKING: gate prefix(es) {uncovered} match no "
                  "comparable row -- part of the hot-path gate would check "
                  "nothing (renamed bench rows? stale baseline?)")
            return 1
    flagged = [r for r in rows if r[5]]
    # gated rows: paired-sample permutation verdicts (fail closed on
    # missing samples -- a gate that cannot test is a failing gate)
    verdicts = {}
    for name, *_ in rows:
        if gated(name):
            verdicts[name] = gate_verdict(base[name], fresh[name],
                                          args.max_regress, args.alpha)
    blocked = [n for n, v in verdicts.items() if v[1]]
    width = max(len(r[0]) for r in rows)
    print(f"# regression report: baseline={args.baseline} "
          f"tolerance={args.tolerance}x"
          + (f" gate={args.max_regress}x alpha={args.alpha}"
             if args.max_regress else "")
          + f" ({len(rows)} comparable rows)")
    print(f"{'name':<{width}}  metric    baseline      fresh      ratio")
    for name, metric, bv, fv, ratio, bad in rows:
        if name in verdicts:
            _, is_blocked, why = verdicts[name]
            mark = f"  << GATE: {why}" if is_blocked else f"  [{why}]"
        else:
            mark = "  << REGRESSION" if bad else ""
        print(f"{name:<{width}}  {metric:<8}{bv:>10.3f} {fv:>10.3f} "
              f"{ratio:>9.2f}x{mark}")
    if blocked:
        print(f"# BLOCKING: {len(blocked)} hot-path row(s) failed the "
              f"{args.max_regress}x permutation gate: {blocked}")
        return 1
    if flagged:
        print(f"# {len(flagged)} row(s) above the {args.tolerance}x band")
        return 1 if args.strict else 0
    print("# all rows within the tolerance band")
    return 0


if __name__ == "__main__":
    sys.exit(main())
