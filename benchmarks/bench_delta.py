"""Non-blocking bench regression check: fresh BENCH json vs committed baseline.

Compares the latency knee of a just-produced ``BENCH_serve.json`` against
the committed baseline (``benchmarks/baselines/BENCH_serve.json``) and
prints a GitHub Actions ``::warning::`` annotation when the knee regressed
by more than the threshold — achieved QPS down >20% or knee p95 up >20%.

ALWAYS exits 0: nightly hardware is shared and noisy, so a knee delta is
a signal to look at, not a gate to flake on. The trace artifact uploaded
next to the bench json is the first thing to look *at* — the slow-sample
lifecycle attribution says whether the regression is serving or lifecycle.

  python benchmarks/bench_delta.py BENCH_serve.json \
      --baseline benchmarks/baselines/BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import sys


def _load(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        print(f"bench_delta: cannot read {path}: {e}")
        return None


def compare_chaos(fresh: dict, base: dict) -> list[str]:
    """Chaos artifacts: the fault counters are DETERMINISTIC (the plan
    scripts every fault by call number), so any drift at all — one more
    retry, one fewer quarantine — means the failure semantics changed
    and is flagged; there is no noise threshold to hide behind."""
    fc, bc = fresh.get("fault_counters"), base.get("fault_counters")
    if not fc or not bc:
        return [f"fault_counters missing (fresh={bool(fc)}, "
                f"baseline={bool(bc)})"]
    warnings = []
    for key in sorted(set(fc) | set(bc)):
        fv, bv = fc.get(key), bc.get(key)
        if fv != bv:
            warnings.append(
                f"fault-path count {key!r} changed: {bv} -> {fv} "
                f"(deterministic — this is a semantics change, not noise)")
    return warnings


def compare_allpairs(fresh: dict, base: dict,
                     threshold: float = 0.20) -> list[str]:
    """All-pairs artifacts: the prefiltered pipeline's score-phase
    throughput down more than the threshold is flagged."""
    fv = (fresh.get("device") or {}).get("pairs_per_sec", 0.0)
    bv = (base.get("device") or {}).get("pairs_per_sec", 0.0)
    if bv > 0 and fv < (1 - threshold) * bv:
        return [f"device score-phase pairs/s regressed "
                f"{100 * (1 - fv / bv):.0f}%: {fv:.0f} vs baseline {bv:.0f}"]
    return []


def compare(fresh: dict, base: dict, threshold: float = 0.20) -> list[str]:
    """Return warning strings for every knee metric past the threshold."""
    if fresh.get("bench") == "chaos_soak" or "fault_counters" in fresh:
        return compare_chaos(fresh, base)
    if fresh.get("bench") == "allpairs":
        return compare_allpairs(fresh, base, threshold)
    warnings = []
    fk, bk = fresh.get("knee"), base.get("knee")
    if not fk or not bk:
        return [f"knee missing (fresh={bool(fk)}, baseline={bool(bk)}) — "
                f"the sweep found no absorbed rate"]
    qps_f, qps_b = fk.get("achieved_qps", 0.0), bk.get("achieved_qps", 0.0)
    if qps_b > 0 and qps_f < (1 - threshold) * qps_b:
        warnings.append(
            f"knee achieved QPS regressed {100 * (1 - qps_f / qps_b):.0f}%: "
            f"{qps_f:.1f} vs baseline {qps_b:.1f}")
    p95_f, p95_b = fk.get("p95_ms", 0.0), bk.get("p95_ms", 0.0)
    if p95_b > 0 and p95_f > (1 + threshold) * p95_b:
        warnings.append(
            f"knee p95 regressed {100 * (p95_f / p95_b - 1):.0f}%: "
            f"{p95_f:.1f}ms vs baseline {p95_b:.1f}ms")
    # compiles are deterministic (no noise excuse): ANY growth is a flag
    nc_f = sum(fresh.get("jit_compiles", {}).values())
    nc_b = sum(base.get("jit_compiles", {}).values())
    if nc_b and nc_f > nc_b:
        warnings.append(
            f"pre-sweep compile count grew {nc_b} -> {nc_f} "
            f"({fresh.get('jit_compiles')}) — a shape or cache key changed")
    return warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("fresh", help="BENCH json produced by this run")
    ap.add_argument("--baseline",
                    default="benchmarks/baselines/BENCH_serve.json")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="relative knee regression that triggers a warning")
    args = ap.parse_args(argv)

    fresh, base = _load(args.fresh), _load(args.baseline)
    if fresh is None or base is None:
        return 0    # missing artifact: nothing to compare, never block
    warnings = compare(fresh, base, args.threshold)
    chaos = fresh.get("bench") == "chaos_soak" or "fault_counters" in fresh
    allpairs = fresh.get("bench") == "allpairs"
    title = ("chaos fault-count drift" if chaos
             else "allpairs throughput regression" if allpairs
             else "serve_slo knee regression")
    for w in warnings:
        print(f"::warning title={title}::{w}")
    if not warnings and chaos:
        print(f"bench_delta: chaos fault counters identical to baseline "
              f"({len(fresh.get('fault_counters', {}))} counters)")
    elif not warnings and allpairs:
        dv = (fresh.get("device") or {}).get("pairs_per_sec", 0.0)
        print(f"bench_delta: allpairs throughput within "
              f"{args.threshold:.0%} of baseline (device {dv:.0f} pairs/s)")
    elif not warnings:
        fk, bk = fresh["knee"], base["knee"]
        print(f"bench_delta: knee within {args.threshold:.0%} of baseline "
              f"(achieved {fk['achieved_qps']:.1f} vs {bk['achieved_qps']:.1f}"
              f" q/s, p95 {fk['p95_ms']:.1f} vs {bk['p95_ms']:.1f} ms)")
    return 0        # non-blocking by design (see module docstring)


if __name__ == "__main__":
    sys.exit(main())
