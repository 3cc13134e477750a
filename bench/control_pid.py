#!/usr/bin/env python3
"""Read the control of a percent-identity clustering cell: the plain
reference (``harness/reference_pid.py``) computed in bfloat16 where the
configuration states int32 (signature accumulators, DP cells) and its
walk compared with the exact reference's, number by number, as a run
compares each clustering of its window. The comparison must reject it.

    python3 bench/control_pid.py --workload ecoli_pid_cluster --seeds 11,12,13

One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    from harness.allpairs_pid import control
    from harness.common import device_info, read_json, use_compile_cache
    use_compile_cache()
    bench = read_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = read_json(ROOT / conf["file"])
    dev = device_info()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        got = control(cfg, seed)
        print(json.dumps(dict(workload=args.workload, seed=seed, **got,
                              seconds=time.perf_counter() - t,
                              device=dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
