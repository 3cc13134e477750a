#!/usr/bin/env python3
"""Read the control of a cell: the plain reference put in the program's
place and computed in bfloat16 where the configuration states int32
(signature accumulators, DP lanes), at the cell's own size. The numbers a
run compares for ``correct`` must reject it.

    python3 bench/control.py --workload ecoli_cluster --seeds 11,12,13 \
        --seconds 30

For all-pairs the control's one clustering is compared with the exact
reference's, number by number, as a run compares each clustering of its
window. For serving, the control answers every query of the cell's pool;
``wrong_answers`` counts the requests of the seed's window (the cell's
traffic for ``--seconds``) whose answer would differ. One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def allpairs_control(cfg: dict, seed: int) -> dict:
    from harness import gen, reference
    from harness.allpairs import compare
    corpus = gen.family_corpus(seed, **cfg["corpus"])
    want = reference.allpairs(corpus["ids"], corpus["lens"], cfg)
    low = reference.allpairs(corpus["ids"], corpus["lens"], cfg, low=True)
    return compare(low, want)


def serve_control(cfg: dict, tr: dict, seed: int, seconds: float) -> dict:
    import numpy as np

    from harness import gen, reference, traffic
    d = gen.protein_sets(seed, n_queries=tr["pool"], **cfg["refs"],
                         **cfg["queries"])
    lsh = cfg["lsh"]
    kw = dict(k=lsh["k"], T=lsh["T"], f=lsh["f"], scheme=lsh["scheme"])
    out = {}
    for low in (False, True):
        r_sig, r_ok = reference.signatures(d["ref_ids"], d["ref_lens"],
                                           low=low, **kw)
        q_sig, q_ok = reference.signatures(d["query_ids"], d["query_lens"],
                                           low=low, **kw)
        out[low] = reference.answers(r_sig, r_ok, q_sig, q_ok, f=lsh["f"],
                                     bands=lsh["d"] + 1, k=cfg["k"])
    bad = np.any((out[True][0] != out[False][0])
                 | (out[True][1] != out[False][1]), axis=1)
    due = traffic.arrivals(tr, seed, seconds)
    pick = gen.rng_for(seed, 4).permutation(len(due)) % tr["pool"]
    return dict(wrong_answers=int(bad[pick].sum()), requests=len(due),
                pool_wrong=int(bad.sum()), pool=tr["pool"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    from harness.common import device_info, read_json, use_compile_cache
    use_compile_cache()
    bench = read_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = read_json(ROOT / conf["file"])
    tr = read_json(BENCH_DIR / "traffic" / f"{wl['traffic']}.json")
    dev = device_info()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        if cfg["operation"] == "allpairs":
            got = allpairs_control(cfg, seed)
        else:
            got = serve_control(cfg, tr, seed, args.seconds)
        print(json.dumps(dict(workload=args.workload, seed=seed, **got,
                              seconds=time.perf_counter() - t,
                              device=dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
