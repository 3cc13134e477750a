"""Many-against-many driver: corpus -> similarity graph -> protein families.

  PYTHONPATH=src python -m repro.launch.allpairs \
      --n-families 64 --family-size 4 --n-singletons 256 --d 1 \
      --min-pid 50 [--out /tmp/families.npz] [--score-only] [--stats] \
      [--incremental 128]

Builds (or loads, --index) the corpus SignatureIndex, runs the LSH
self-join, scores the candidate pairs with device-resident
Smith-Waterman waves (fused gather + ungapped X-drop prefilter + async
drain ring), and clusters the thresholded similarity graph into families.

``--incremental N`` holds the last N sequences out of the batch run and
ingests them afterwards through the append-only lifecycle: the index
grows by a sealed segment, the DELTA self-join emits only new-vs-resident
pairs from the touched buckets, only those pairs are scored, and the
surviving edges union into the persistent disjoint-set forest — families
equal a from-scratch recluster at delta cost. With a directory --index
the forest persists beside the manifest as ``families.npz``.

Band keys are splitmix-mixed before bucketing (the serving default,
exactness-preserving); the signature scheme itself stays ``java`` here
because the self-join's Hamming threshold is calibrated to the java
hash's compressed distance scale (``--scheme splitmix`` needs a larger
``--d``).
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="all-pairs corpus similarity search (repro.allpairs)")
    ap.add_argument("--n-families", type=int, default=64)
    ap.add_argument("--family-size", type=int, default=4)
    ap.add_argument("--n-singletons", type=int, default=256)
    ap.add_argument("--len-mean", type=int, default=200)
    ap.add_argument("--sub-rate", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d", type=int, default=1,
                    help="Hamming threshold for the candidate filter")
    ap.add_argument("--scheme", default="java",
                    choices=["java", "splitmix"],
                    help="signature hash bits. Stays java here (unlike the "
                         "serving CLIs): the self-join's d threshold is "
                         "calibrated to the java hash's compressed distance "
                         "scale — splitmix's honest bits need a larger --d")
    ap.add_argument("--no-hamming-filter", action="store_true",
                    help="score every band collision (no distance filter)")
    ap.add_argument("--prefilter", action="store_true",
                    help="skip full SW for pairs whose best ungapped "
                         "diagonal run scores < --prefilter-min. Opt-in "
                         "here: the ungapped score is a LOWER bound of the "
                         "gapped score, and for indel-rich homologs (runs "
                         "chopped by gaps) it can fall below any useful "
                         "threshold — calibrate on your corpus (the "
                         "benchmark corpus keeps 100% recall at 40)")
    ap.add_argument("--prefilter-min", type=int, default=40,
                    help="ungapped score below which full SW is skipped")
    ap.add_argument("--xdrop", type=int, default=None,
                    help="finite X-drop margin (default: best ungapped run)")
    ap.add_argument("--fuse-prefilter", action="store_true",
                    help="run the ungapped prefilter INSIDE the self-join "
                         "(rejected pairs never reach the host); same "
                         "thresholds as --prefilter, identical survivors")
    ap.add_argument("--gap-mode", default="linear",
                    choices=["linear", "affine"],
                    help="gap model: linear (-4/residue) or affine Gotoh "
                         "(open/extend; needs --score-only, PID waves "
                         "stay linear)")
    ap.add_argument("--gap-open", type=int, default=None,
                    help="affine gap-open score (default -11)")
    ap.add_argument("--gap-extend", type=int, default=None,
                    help="affine gap-extend score (default -1)")
    ap.add_argument("--min-pid", type=float, default=50.0,
                    help="percent-identity threshold for family edges")
    ap.add_argument("--shards", type=int, default=1,
                    help="bucket shards: the self-join emits each shard's "
                         "buckets' pairs on its own device (mix32(key) %% "
                         "n_shards ownership). Off TPU, score-only waves "
                         "(--score-only or --prefilter's ungapped phase) "
                         "additionally split over that many devices as one "
                         "SPMD program; PID waves (the default scoring "
                         "mode here) and the TPU's Pallas waves stay "
                         "single-device")
    ap.add_argument("--wave-batch", type=int, default=64)
    ap.add_argument("--score-only", action="store_true",
                    help="score waves without PID: families threshold on "
                         "SW score >= --min-score")
    ap.add_argument("--min-score", type=int, default=60,
                    help="SW score threshold used with --score-only")
    ap.add_argument("--index", default=None,
                    help="reuse/persist the corpus index here (.npz = "
                         "legacy monolithic; otherwise a segment directory "
                         "with O(delta) appends)")
    ap.add_argument("--incremental", type=int, default=0, metavar="N",
                    help="hold the last N sequences out of the batch run "
                         "and ingest them afterwards via the delta "
                         "self-join + persistent family forest (families "
                         "equal the from-scratch recluster, at delta cost)")
    ap.add_argument("--out", default=None,
                    help="write edges + labels npz here")
    ap.add_argument("--stats", action="store_true",
                    help="print per-band bucket occupancy before joining")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write this process's metrics registry here: "
                         ".json = mergeable registry_state snapshot (what "
                         "--metrics-merge consumes), anything else = "
                         "Prometheus text exposition")
    ap.add_argument("--metrics-merge", nargs="*", default=None,
                    metavar="JSON",
                    help="fold worker registry_state JSON snapshots "
                         "(written by their --metrics-out *.json) into "
                         "this process's registry before rendering "
                         "--metrics-out — histogram buckets add exactly, "
                         "so N workers aggregate into the true fleet "
                         "histogram")
    args = ap.parse_args(argv)

    import os

    if args.shards > 1 and "XLA_FLAGS" not in os.environ:
        # must precede the first jax import (host platform device count)
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={args.shards}"

    import numpy as np

    from ..allpairs import (AllPairsConfig, WaveConfig, all_pairs_ingest,
                            all_pairs_search, forest_from_result)
    from ..core import LSHConfig
    from ..data import FamilyCorpusConfig, make_family_corpus
    from ..index import SignatureIndex, occupancy_report

    import jax

    if args.shards > 1 and jax.device_count() < args.shards:
        # no silent fallback: the self-join would run its one-device vmap
        # path and waves would clamp to one device
        raise SystemExit(
            f"--shards {args.shards} needs that many devices; this "
            f"process sees {jax.device_count()} "
            f"{jax.devices()[0].platform} device(s) (on a CPU host, "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{args.shards} provides them)")

    corpus = make_family_corpus(FamilyCorpusConfig(
        n_families=args.n_families, family_size=args.family_size,
        n_singletons=args.n_singletons, len_mean=args.len_mean,
        sub_rate=args.sub_rate, seed=args.seed))
    ids, lens, labels = corpus["ids"], corpus["lens"], corpus["labels"]
    n = len(lens)
    lsh = LSHConfig(k=3, T=13, f=32, d=args.d, scheme=args.scheme)

    index = None
    if args.index and os.path.exists(args.index):
        t0 = time.time()
        index = SignatureIndex.load(args.index, expected_cfg=lsh)
        print(f"[index] loaded {index.size} sigs in {time.time()-t0:.2f}s "
              f"(fp={index.fingerprint})")
    cfg = AllPairsConfig(
        lsh=lsh, hamming_filter=not args.no_hamming_filter,
        min_pid=args.min_pid, min_score=args.min_score,
        n_shards=args.shards,
        wave=WaveConfig(wave_batch=args.wave_batch,
                        with_pid=not args.score_only,
                        n_devices=args.shards,
                        prefilter=args.prefilter,
                        prefilter_min=args.prefilter_min,
                        xdrop=args.xdrop,
                        gap_mode=args.gap_mode,
                        gap_open=args.gap_open,
                        gap_extend=args.gap_extend),
        fuse_prefilter=args.fuse_prefilter)

    def _emit_metrics():
        from ..obs import REGISTRY, merge_registry_state, registry_state
        if args.metrics_merge:
            import json
            for path in args.metrics_merge:
                with open(path) as fh:
                    merge_registry_state(json.load(fh))
            print(f"[metrics] merged {len(args.metrics_merge)} worker "
                  f"snapshot(s)")
        if args.metrics_out:
            if str(args.metrics_out).endswith(".json"):
                import json
                with open(args.metrics_out, "w") as fh:
                    json.dump(registry_state(REGISTRY), fh)
            else:
                with open(args.metrics_out, "w") as fh:
                    fh.write(REGISTRY.prometheus())
            print(f"[metrics] wrote {args.metrics_out}")

    # ---- incremental mode: batch the resident corpus, ingest the rest
    if args.incremental:
        base = n - args.incremental
        if base <= 0:
            raise SystemExit(f"--incremental {args.incremental} leaves no "
                             f"resident corpus (total {n} seqs)")
        if index is not None and index.size != base:
            print(f"[index] loaded index covers {index.size} != resident "
                  f"{base} seqs; rebuilding")
            index = None
        t0 = time.time()
        res = all_pairs_search(ids[:base], lens[:base], cfg, index=index)
        t_batch = time.time() - t0
        forest = forest_from_result(res)
        t0 = time.time()
        ing = all_pairs_ingest(ids, lens, base, cfg, index=res.index,
                               forest=forest)
        t_ingest = time.time() - t0
        print(f"[batch]  {base} seqs -> {res.join.n_candidates} pairs, "
              f"{res.families.n_families} families ({t_batch:.2f}s)")
        print(f"[ingest] +{args.incremental} seqs -> "
              f"{ing.join.n_candidates} DELTA pairs "
              f"(epoch {res.index.epoch}), "
              f"{int(ing.edge_mask.sum())} edges survived "
              f"({t_ingest:.2f}s vs {t_batch:.2f}s batch — the "
              f"resident corpus was never re-joined or re-scored)")
        fams = ing.families
        pure = sum(1 for fam in fams if len(set(labels[fam])) == 1)
        largest = max((len(f) for f in fams), default=0)
        print(f"[truth]  {pure}/{len(fams)} families over the grown corpus "
              f"are pure; largest={largest}")
        if args.index:
            n_seg = res.index.save(args.index)
            msg = f"[index]  persisted to {args.index} ({n_seg} file(s))"
            if not str(args.index).endswith(".npz"):
                fpath = os.path.join(args.index, "families.npz")
                # the forest lives beside the manifest, stamped with the
                # generation it was clustered against
                forest.save(fpath, generation=res.index.generation)
                msg += f" + forest {fpath}"
            print(msg)
        if args.out:
            pairs = np.concatenate([res.pairs, ing.join.pairs], axis=0)
            scores = np.concatenate([res.scored.scores, ing.scored.scores])
            payload = dict(pairs=pairs, scores=scores,
                           labels=ing.labels, truth=labels)
            if res.scored.pid is not None and ing.scored.pid is not None:
                payload["pid"] = np.concatenate([res.scored.pid,
                                                 ing.scored.pid])
            np.savez_compressed(args.out, **payload)
            print(f"[out]    wrote {args.out}")
        _emit_metrics()
        return

    t0 = time.time()
    res = all_pairs_search(ids, lens, cfg, index=index)
    wall = time.time() - t0
    if args.stats:
        print(occupancy_report(res.index))
    if args.index and index is None:
        res.index.save(args.index)
        print(f"[index] persisted to {args.index}")

    sc = res.scored
    print(f"[join]  {n} seqs -> {res.join.n_candidates} candidate pairs "
          f"({res.join.n_candidates / max(n*(n-1)//2, 1):.2%} of all pairs)")
    print(f"[score] {sc.n_waves} SW waves over {sc.n_shapes} fixed shapes"
          f"{' (score-only)' if args.score_only else ''}"
          + (f"; prefilter rejected {sc.n_prefiltered}/{len(res.pairs)} "
             f"({sc.n_prefiltered / max(len(res.pairs), 1):.0%})"
             if sc.kept is not None else ""))
    thresh = (f"SW score >= {args.min_score}" if args.score_only
              else f"{args.min_pid:.0f}% PID")
    print(f"[graph] {int(res.families.edge_mask.sum())} edges at {thresh} "
          f"-> {res.families.n_families} families (total {wall:.2f}s)")

    # ground-truth purity (synthetic corpora only)
    pure = sum(1 for fam in res.families.families
               if len(set(labels[fam])) == 1)
    largest = max((len(f) for f in res.families.families), default=0)
    print(f"[truth] {pure}/{res.families.n_families} discovered families "
          f"are pure; largest={largest}")

    if args.out:
        payload = dict(pairs=res.pairs, scores=sc.scores,
                       labels=res.labels, truth=labels)
        if sc.pid is not None:
            payload["pid"] = sc.pid
        np.savez_compressed(args.out, **payload)
        print(f"[out]   wrote {args.out}")
    _emit_metrics()


if __name__ == "__main__":
    from ..util import use_compile_cache
    use_compile_cache()
    main()
