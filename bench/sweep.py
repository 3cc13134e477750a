#!/usr/bin/env python3
"""Find the knee of a serving configuration on the chip: the highest
offered Poisson rate the system sustains.

    python3 bench/sweep.py --config swissprot_serve --seed 7 \
        --rates 250,500,1000,2000 --seconds 10

One process builds and warms the service once, then offers each rate for
``--seconds``. A rate is sustained when the window's requests completed
inside the window number at least 0.95 of those offered and the backlog
does not grow: the median latency of the window's last third is under
twice that of its first third. The sweep stops after the second rate
that is not sustained. One JSON line per rate; run it where the cell
runs, since the knee is the chip's.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--pool", type=int, default=4096)
    args = ap.parse_args(argv)

    import numpy as np
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import gen, traffic
    from harness.common import device_info, read_json, use_compile_cache
    use_compile_cache()
    from harness.serve import Service, outcomes
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"needs a TPU; JAX found {dev['platform']}", file=sys.stderr)
        return 3
    cfg = read_json(BENCH_DIR / "configs" / f"{args.config}.json")
    t = time.perf_counter()
    svc = Service(cfg, args.seed, args.pool)
    print(json.dumps({"setup_s": time.perf_counter() - t, "device": dev}),
          flush=True)
    misses = 0
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = {"phases": [{"rate_qps": rate, "share": 1.0}]}
        due = traffic.arrivals(mix, args.seed, args.seconds)
        pick = gen.rng_for(args.seed, 4).permutation(len(due)) % args.pool
        t0 = time.perf_counter() + 0.001
        client = svc.offer(due, pick, t0)
        end = time.perf_counter()
        lat, kind = outcomes(client, 0.0, end)
        done = kind == "completed"
        in_window = done & (client.done <= t0 + args.seconds)
        third = len(due) // 3
        first = float(np.median(lat[:third]))
        last = float(np.median(lat[-third:]))
        ok = in_window.sum() >= 0.95 * len(due) and last < 2 * first
        misses += not ok
        print(json.dumps({
            "offered_qps": rate, "offered": len(due),
            "completed_in_window": int(in_window.sum()),
            "completed": int(done.sum()),
            "p50_ms": float(np.quantile(lat, 0.5)),
            "p95_ms": float(np.quantile(lat, 0.95)),
            "p99_ms": float(np.quantile(lat, 0.99)),
            "p50_first_third_ms": first, "p50_last_third_ms": last,
            "client_late_p99_ms": float(np.nanquantile(client.late_s, 0.99)
                                        * 1e3),
            "sustained": bool(ok)}), flush=True)
        if misses >= 2:
            break
        time.sleep(1.0)
    svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
