"""Sharding benchmark — bucket-sharded probe/self-join + SPMD waves vs
``n_shards=1``, on XLA-forced host devices (or a real mesh).

Acceptance criteria of the bucket-partition substrate:

* the sharded self-join's pair set must be bit-identical to the
  single-shard join, and the SPMD wave scores bit-identical to the
  single-device wave (asserted here, not just in tests);
* with forced host devices, sharded self-join + multi-device waves must
  beat ``n_shards=1`` end-to-end (self-join + scoring) in wall-clock —
  there is no dense-sweep fallback left to hide behind: `ShardedIndex`
  only has the bucket-probe ring, and the self-join takes the shard_map
  path whenever the process has ``n_shards`` devices (asserted).

Emits ``BENCH_shard.json`` (probe + self-join + wave wall-clock vs
``n_shards``, speedups) which the nightly CI job uploads, so the scaling
trajectory is tracked across PRs.

  PYTHONPATH=src python -m benchmarks.sharding --smoke      # CI (4 devices)
  PYTHONPATH=src python -m benchmarks.sharding --n-seqs 2048 --shards 4

(XLA_FLAGS is set before the first jax import; pass --shards to change
the forced host device count.)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _run(args):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.allpairs import WaveConfig, lsh_self_join, score_pairs
    from repro.core import LSHConfig, ScalLoPS
    from repro.data import FamilyCorpusConfig, make_family_corpus
    from repro.index import ShardedIndex, SignatureIndex
    from repro.index.service import topk_probe

    S = args.shards
    assert jax.device_count() >= S, (
        f"need {S} devices for the shard_map paths (no silent fallback), "
        f"got {jax.devices()}")
    csv = print
    csv("bench,n_seqs,n_shards,metric,value")
    n = args.n_seqs
    n_fam = n // 8
    corpus = make_family_corpus(FamilyCorpusConfig(
        n_families=n_fam, family_size=4, n_singletons=n - 4 * n_fam,
        len_mean=150, len_std=25, sub_rate=0.03, seed=42))
    ids, lens = corpus["ids"], corpus["lens"]
    lsh = LSHConfig(k=3, T=13, f=32, d=1)
    index = SignatureIndex.build(lsh, ids, lens)
    index._ensure_built()

    def timed(fn, reps=args.reps):
        fn()                            # warm (compile + caches)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            ts.append(time.perf_counter() - t0)
        return min(ts), out

    results = {"bench": "sharding", "n_seqs": n, "n_shards": S,
               "devices": jax.device_count()}

    # ---- probe serving: bucket-probe ring vs n_shards -------------------
    q_sigs = ScalLoPS(lsh).signatures(ids[:args.n_queries],
                                      lens[:args.n_queries])
    t_probe1, base = timed(lambda: topk_probe(index, q_sigs, k=8, cap=64))
    csv(f"sharding,{n},1,probe_batch_s,{t_probe1:.4f}")
    probe = {"1": round(t_probe1, 4)}
    for s in (2, S) if S != 2 else (S,):
        sh = ShardedIndex(index, Mesh(np.array(jax.devices()[:s]),
                                      ("data",)))
        t_probe, got = timed(lambda sh=sh: sh.topk(q_sigs, k=8, cap=64))
        np.testing.assert_array_equal(np.asarray(base[0]), got[0])
        np.testing.assert_array_equal(np.asarray(base[1]), got[1])
        csv(f"sharding,{n},{s},probe_batch_s,{t_probe:.4f}")
        probe[str(s)] = round(t_probe, 4)
    results["probe_batch_s"] = probe
    csv(f"sharding,{n},{S},probe_bitexact,1")

    # ---- self-join: shard_map bucket emission vs n_shards ---------------
    t_join1, join1 = timed(lambda: lsh_self_join(index, max_pairs=1 << 14))
    csv(f"sharding,{n},1,selfjoin_s,{t_join1:.4f}")
    csv(f"sharding,{n},1,candidates,{join1.n_candidates}")
    t_joinS, joinS = timed(
        lambda: lsh_self_join(index, max_pairs=1 << 14, n_shards=S))
    np.testing.assert_array_equal(join1.pairs, joinS.pairs)
    csv(f"sharding,{n},{S},selfjoin_s,{t_joinS:.4f}")
    csv(f"sharding,{n},{S},selfjoin_bitexact,1")
    results["selfjoin_s"] = {"1": round(t_join1, 4), str(S): round(t_joinS, 4)}
    results["candidates"] = int(join1.n_candidates)

    # ---- SW waves: SPMD split vs single device --------------------------
    # full-SW waves (no prefilter): the DP-bound phase whose scaling the
    # split exists for — the prefiltered pipeline is benchmarked in
    # benchmarks/allpairs.py and its ungapped scans split the same way
    wave = WaveConfig(wave_batch=64, inflight=2)
    wave1 = dataclasses.replace(wave, n_devices=1)
    waveS = dataclasses.replace(wave, n_devices=S)
    t_score1, s1 = timed(lambda: score_pairs(ids, lens, join1.pairs, wave1))
    t_scoreS, sS = timed(lambda: score_pairs(ids, lens, join1.pairs, waveS))
    np.testing.assert_array_equal(s1.scores, sS.scores)
    np.testing.assert_array_equal(s1.kept, sS.kept)
    csv(f"sharding,{n},1,score_s,{t_score1:.4f}")
    csv(f"sharding,{n},{S},score_s,{t_scoreS:.4f}")
    csv(f"sharding,{n},{S},score_bitexact,1")
    csv(f"sharding,{n},{S},speedup_score,{t_score1 / t_scoreS:.2f}")
    results["score_s"] = {"1": round(t_score1, 4), str(S): round(t_scoreS, 4)}

    # ---- end-to-end: self-join + scoring --------------------------------
    t1 = t_join1 + t_score1
    tS = t_joinS + t_scoreS
    speedup = t1 / tS
    csv(f"sharding,{n},1,e2e_s,{t1:.4f}")
    csv(f"sharding,{n},{S},e2e_s,{tS:.4f}")
    csv(f"sharding,{n},{S},speedup_e2e,{speedup:.2f}")
    results["e2e_s"] = {"1": round(t1, 4), str(S): round(tS, 4)}
    results["speedup"] = {"score": round(t_score1 / t_scoreS, 2),
                          "e2e": round(speedup, 2)}
    results["exactness"] = {"probe_bitexact": True,
                            "selfjoin_bitexact": True,
                            "score_bitexact": True}

    with open(args.json, "w") as fh:
        json.dump(results, fh, indent=2)
    csv(f"sharding,{n},{S},json_written,{args.json}")

    assert speedup > 1.0, (
        f"sharded self-join + multi-device waves must beat n_shards=1 "
        f"end-to-end (got {speedup:.2f}x at n_shards={S} on "
        f"{jax.device_count()} devices)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus for CI (writes BENCH_shard.json)")
    ap.add_argument("--n-seqs", type=int, default=None)
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--json", default="BENCH_shard.json")
    args = ap.parse_args(argv)
    args.n_seqs = args.n_seqs or (512 if args.smoke else 2048)
    if args.shards < 2:
        ap.error("--shards must be >= 2 (the benchmark compares against "
                 "n_shards=1)")

    if "XLA_FLAGS" not in os.environ:
        # must precede the first jax import (host platform device count)
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={args.shards}"
        if "jax" in sys.modules:
            raise RuntimeError("jax imported before XLA_FLAGS was set; "
                               "run benchmarks.sharding as the entry point")
    _run(args)


if __name__ == "__main__":
    main()
