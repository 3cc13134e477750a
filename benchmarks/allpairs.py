"""All-pairs benchmark — the tiled wave pipeline vs naive pairwise, on CPU.

Exactness checks of the `repro.allpairs` subsystem on a 2048-sequence
synthetic corpus (CPU timings here are not device numbers; the chip's are
in PERF.md and the ledger):

* the self-join's candidate pair set must EXACTLY match brute-force
  enumeration of LSH band collisions;
* the ungapped X-drop prefilter must keep >= 99% of the pairs whose full
  SW score reaches the family threshold, and its survivors' scores must
  equal the unfiltered pipeline's;
* wave scores must equal per-pair Smith-Waterman on a random slice;
* the tiled pipeline must beat naive all-pairs per-pair Smith-Waterman by
  >= 10x wall-clock (timed on a sample, extrapolated). The naive baseline
  deliberately pays the per-shape jit retrace on every ragged pair — that
  cache-thrash IS the modeled cost of shipping unpadded per-pair DP calls,
  exactly what the padded-ladder scheduler exists to remove.

CSV: bench,n_seqs,method,metric,value.  ``--json`` (implied by ``--smoke``)
additionally writes BENCH_allpairs.json — pairs/sec, waves, prefilter
reject rate, wall-clock — which the nightly CI job uploads.
``--profile`` reports the wave (by kind) and drain time split from the
``wave`` and ``drain`` spans.
"""
from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from repro.align.smith_waterman import sw_score
from repro.allpairs import (brute_force_collisions, lsh_self_join,
                            score_pairs, wave_plan, WaveConfig)
from repro.core import LSHConfig
from repro.data import FamilyCorpusConfig, make_family_corpus
from repro.index import SignatureIndex

# Family score threshold for the recall criterion, calibrated on the
# planted-family corpus (len_mean=150, sub_rate=0.03): true family pairs
# score >= ~390 while band-collision noise tops out at ~105 — 150 separates
# them with margin on both sides (see tests/test_allpairs.py recall test).
FAMILY_SCORE_T = 150

FULL_WAVE = WaveConfig(wave_batch=64, inflight=2)
DEVICE_WAVE = WaveConfig(wave_batch=64, prefilter=True, prefilter_min=40,
                         inflight=2)


def _warm(ids, lens, pairs, cfg: WaveConfig):
    """Compile every wave shape of ``cfg`` ahead of the timed run: one pair
    per (Lq, Lr) ladder bucket, with the prefilter threshold floored so the
    full-SW shapes compile too."""
    sample = np.array(sorted({int(idx[0]) for idx, _, _ in
                              wave_plan(pairs, lens, cfg)}))
    if len(sample) == 0:
        return
    wc = dataclasses.replace(cfg, prefilter_min=-(1 << 30)) \
        if cfg.prefilter else cfg
    score_pairs(ids, lens, pairs[sample], wc)


def _span_split(ids, lens, pairs, wc):
    """Seconds of ``score_pairs``' wave (by kind) and drain spans, sorted
    by name. With ``wc.profile`` each wave blocks inside its span, so the
    wave spans hold the device time."""
    from repro.obs import TRACER
    TRACER.clear()
    TRACER.enable()
    try:
        score_pairs(ids, lens, pairs, wc)
    finally:
        TRACER.disable()
    split: dict[str, float] = {}
    for sp in TRACER.spans():
        if sp["name"] == "wave":
            key = f"wave_{sp['args']['kind']}"
        elif sp["name"] == "drain":
            key = sp["name"]
        else:
            continue
        split[key] = split.get(key, 0.0) + sp["dur"]
    TRACER.clear()
    return sorted(split.items())


def run(csv=print, n_seqs: int = 2048, naive_sample: int = 192,
        profile: bool = False, json_path: str | None = None):
    csv("bench,n_seqs,method,metric,value")
    n_fam = n_seqs // 8                    # 4-member families, half singletons
    corpus = make_family_corpus(FamilyCorpusConfig(
        n_families=n_fam, family_size=4, n_singletons=n_seqs - 4 * n_fam,
        len_mean=150, len_std=25, sub_rate=0.03, seed=42))
    ids, lens = corpus["ids"], corpus["lens"]
    n = len(lens)
    cfg = LSHConfig(k=3, T=13, f=32, d=1)

    # ---- self-join: exactness vs brute-force collision enumeration ------
    t0 = time.time()
    index = SignatureIndex.build(cfg, ids, lens)
    index._ensure_built()
    t_build = time.time() - t0
    csv(f"allpairs,{n},tiled,index_build_s,{t_build:.3f}")

    t0 = time.time()
    join = lsh_self_join(index, max_pairs=1 << 14)   # raw band collisions
    t_join = time.time() - t0
    csv(f"allpairs,{n},tiled,selfjoin_s,{t_join:.3f}")
    csv(f"allpairs,{n},tiled,candidates,{join.n_candidates}")

    want = brute_force_collisions(index)
    got = {tuple(p) for p in join.pairs}
    exact = got == want
    csv(f"allpairs,{n},tiled,collision_exact,{int(exact)}")
    assert exact, (f"self-join diverged from brute-force collisions: "
                   f"{len(got)} vs {len(want)} pairs")

    # ---- full pipeline: every candidate through the DP waves ------------
    _warm(ids, lens, join.pairs, FULL_WAVE)
    t0 = time.time()
    s_full = score_pairs(ids, lens, join.pairs, FULL_WAVE)
    t_full = time.time() - t0
    csv(f"allpairs,{n},full,score_s,{t_full:.3f}")
    csv(f"allpairs,{n},full,waves,{s_full.n_waves}")

    # ---- prefiltered pipeline: fused gather + prefilter + ring -----------
    _warm(ids, lens, join.pairs, DEVICE_WAVE)
    t0 = time.time()
    s_dev = score_pairs(ids, lens, join.pairs, DEVICE_WAVE)
    t_dev = time.time() - t0
    reject_rate = s_dev.n_prefiltered / max(join.n_candidates, 1)
    csv(f"allpairs,{n},device,score_s,{t_dev:.3f}")
    csv(f"allpairs,{n},device,waves,{s_dev.n_waves}")
    csv(f"allpairs,{n},device,wave_shapes,{s_dev.n_shapes}")
    csv(f"allpairs,{n},device,pairs_per_sec,{join.n_candidates / t_dev:.0f}")
    csv(f"allpairs,{n},device,prefilter_reject_rate,{reject_rate:.4f}")

    # survivors bit-exact with the unfiltered pipeline
    np.testing.assert_array_equal(s_dev.scores[s_dev.kept],
                                  s_full.scores[s_dev.kept])
    csv(f"allpairs,{n},device,survivor_bitexact,1")

    # prefilter recall at the family score threshold
    high = s_full.scores >= FAMILY_SCORE_T
    recall = float(s_dev.kept[high].mean()) if high.any() else 1.0
    csv(f"allpairs,{n},device,recall_at_S{FAMILY_SCORE_T},{recall:.4f}")
    assert recall >= 0.99, (
        f"X-drop prefilter lost {(1 - recall):.1%} of pairs with SW score "
        f">= {FAMILY_SCORE_T} (need >= 99% recall)")
    t_e2e_dev = t_build + t_join + t_dev

    # ---- naive baseline: per-pair SW over ALL pairs (sampled) ------------
    total_pairs = n * (n - 1) // 2
    rng = np.random.default_rng(7)
    ii = rng.integers(0, n, naive_sample)
    jj = rng.integers(0, n, naive_sample)
    sw_score(ids[0][: lens[0]], ids[1][: lens[1]])     # warm one shape
    t0 = time.time()
    for a, b in zip(ii, jj):
        sw_score(ids[a][: lens[a]], ids[b][: lens[b]])
    t_naive_sample = time.time() - t0
    per_pair = t_naive_sample / naive_sample
    t_naive = per_pair * total_pairs
    csv(f"allpairs,{n},naive,per_pair_ms,{per_pair * 1e3:.3f}")
    csv(f"allpairs,{n},naive,total_pairs,{total_pairs}")
    csv(f"allpairs,{n},naive,total_s_extrapolated,{t_naive:.1f}")

    speedup_naive = t_naive / t_e2e_dev
    csv(f"allpairs,{n},device,speedup_vs_naive,{speedup_naive:.1f}")
    assert speedup_naive >= 10, (
        f"tiled all-pairs must beat naive per-pair SW by >= 10x "
        f"(got {speedup_naive:.1f}x)")

    # ---- parity: wave scores == per-pair scores on a random slice --------
    check = join.pairs[rng.permutation(join.n_candidates)[:32]]
    wave_sc = score_pairs(ids, lens, check, FULL_WAVE).scores
    for row, (a, b) in enumerate(check):
        assert wave_sc[row] == sw_score(ids[a][: lens[a]], ids[b][: lens[b]])
    csv(f"allpairs,{n},full,wave_score_parity,1")

    # ---- attribution: wave vs drain split (--profile) --------------------
    if profile:
        for name, wc in (("full", FULL_WAVE), ("device", DEVICE_WAVE)):
            for k, v in _span_split(ids, lens, join.pairs,
                                    dataclasses.replace(wc, profile=True)):
                csv(f"allpairs,{n},{name},profile_{k}_s,{v:.3f}")

    if json_path:
        payload = {
            "bench": "allpairs", "n_seqs": n,
            "candidates": int(join.n_candidates),
            "index_build_s": round(t_build, 3),
            "selfjoin_s": round(t_join, 3),
            "full": {"score_s": round(t_full, 3), "waves": s_full.n_waves},
            "device": {"score_s": round(t_dev, 3), "waves": s_dev.n_waves,
                       "pairs_per_sec": round(join.n_candidates / t_dev, 1),
                       "prefilter_reject_rate": round(reject_rate, 4),
                       "wall_clock_s": round(t_e2e_dev, 3)},
            "speedup": {"vs_naive_extrapolated": round(speedup_naive, 1)},
            "exactness": {"collision_exact": bool(exact),
                          "survivor_bitexact": True,
                          "family_threshold": FAMILY_SCORE_T,
                          "recall_at_family_threshold": round(recall, 4)},
        }
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        csv(f"allpairs,{n},device,json_written,{json_path}")


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus for CI (exercises every code path, "
                         "writes BENCH_allpairs.json)")
    ap.add_argument("--n-seqs", type=int, default=None)
    ap.add_argument("--profile", action="store_true",
                    help="report the wave vs drain time split")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable summary here")
    args = ap.parse_args(argv)
    n = args.n_seqs or (256 if args.smoke else 2048)
    sample = 32 if args.smoke else 192
    json_path = args.json or ("BENCH_allpairs.json" if args.smoke else None)
    run(n_seqs=n, naive_sample=sample, profile=args.profile,
        json_path=json_path)


if __name__ == "__main__":
    main()
