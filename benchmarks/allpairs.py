"""All-pairs benchmark — device-resident wave pipeline vs the PR 2 host
path vs naive pairwise.

Acceptance criteria of the `repro.allpairs` subsystem, measured on a
2048-sequence synthetic corpus:

* the self-join's candidate pair set must EXACTLY match brute-force
  enumeration of LSH band collisions;
* the device-resident pipeline (fused on-device gathers + ungapped X-drop
  prefilter + async drain ring) must beat the PR 2 pipeline (host copy
  loop, synchronous, no prefilter) by >= 3x end-to-end (index build +
  self-join + scoring), with survivor SW scores bit-exact against the PR 2
  path and prefilter recall >= 99% at the family score threshold;
* the wavefront DP (anti-diagonal sweep, `repro.align.gotoh`) must
  deliver >= 2x the row wave's pairs/s at the acceptance shape B=64,
  Lq=Lr=192 (the ``--dp-kernel``/``--gap-mode`` sweep, asserted);
* candidate emission through the fused SpGEMM join (``join_impl="spgemm"``)
  must beat the legacy orchestration (host merge + grow-and-retry) by
  >= 2x warmed steady-state at the FIXED 2048-sequence acceptance corpus —
  the ``--join-impl`` sweep, asserted (like the DP sweep, it runs at the
  acceptance shape even under ``--smoke``), with both impls' pair arrays
  bit-identical;
* the tiled pipeline must beat naive all-pairs per-pair Smith-Waterman by
  >= 10x wall-clock (timed on a sample, extrapolated). The naive baseline
  deliberately pays the per-shape jit retrace on every ragged pair — that
  cache-thrash IS the modeled cost of shipping unpadded per-pair DP calls,
  exactly what the padded-ladder scheduler exists to remove.

CSV: bench,n_seqs,method,metric,value.  ``--json`` (implied by ``--smoke``)
additionally writes BENCH_allpairs.json — pairs/sec, waves, prefilter
reject rate, wall-clock — which the nightly CI job uploads so the perf
trajectory is tracked across PRs.  ``--profile`` reports the host-gather
vs device-DP time split of both pipelines from their ``host_gather``,
``wave`` and ``drain`` spans, making the win attributable.
"""
from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from repro.align import gotoh
from repro.align.smith_waterman import sw_score, sw_scores_device
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

# (dp_kernel, gap_mode) pairs of the score-phase sweep; rowwave+affine is
# rejected by the router and so not a sweep point
DP_SWEEP = (("rowwave", "linear"), ("wavefront", "linear"),
            ("wavefront", "affine"))

PR2_WAVE = WaveConfig(wave_batch=64, device_gather=False, prefilter=False,
                      inflight=0, dp_kernel="rowwave")
DEVICE_WAVE = WaveConfig(wave_batch=64, device_gather=True, prefilter=True,
                         prefilter_min=40, inflight=2)

# the emission sweep's fixed acceptance corpus — the full-size corpus of
# run(); like the DP sweep's fixed (B, L) shape, it does NOT shrink under
# --smoke, because the >= 2x emission criterion is defined at this size
EMISSION_N = 2048


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


def dp_kernel_sweep(csv=print, *, n: int, B: int = 64, L: int = 192,
                    reps: int = 20, dp_kernel: str = "all",
                    gap_mode: str = "all", seed: int = 17) -> dict:
    """Score-phase microbenchmark at the acceptance shape (B=64,
    Lq=Lr=192): warmed steady-state pairs/s of each (dp_kernel, gap_mode)
    sweep point on one device-resident block. The wavefront's win over the
    row wave is an acceptance criterion (>= 2x pairs/s), asserted whenever
    both linear sweep points run."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    qs = jnp.asarray(rng.integers(0, 20, (B, L), dtype=np.int8))
    rs = jnp.asarray(rng.integers(0, 20, (B, L), dtype=np.int8))
    fns = {("rowwave", "linear"): lambda: sw_scores_device(qs, rs),
           ("wavefront", "linear"): lambda: gotoh.sw_wave_linear(qs, rs),
           ("wavefront", "affine"): lambda: gotoh.sw_wave_affine(qs, rs)}
    out = {"shape": {"B": B, "Lq": L, "Lr": L}}
    for kernel, mode in DP_SWEEP:
        if dp_kernel != "all" and kernel != dp_kernel:
            continue
        if gap_mode != "all" and mode != gap_mode:
            continue
        fn = fns[(kernel, mode)]
        fn().block_until_ready()                        # warm the shape
        t0 = time.perf_counter()
        for _ in range(reps):
            fn().block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        key = f"{kernel}_{mode}"
        out[key] = {"wave_ms": round(dt * 1e3, 3),
                    "pairs_per_sec": round(B / dt, 1)}
        csv(f"allpairs,{n},dp_{key},wave_ms,{dt * 1e3:.3f}")
        csv(f"allpairs,{n},dp_{key},pairs_per_sec,{B / dt:.0f}")
    row, wav = out.get("rowwave_linear"), out.get("wavefront_linear")
    if row and wav:
        speedup = wav["pairs_per_sec"] / row["pairs_per_sec"]
        out["speedup_wavefront_vs_rowwave"] = round(speedup, 2)
        csv(f"allpairs,{n},dp_wavefront_linear,speedup_vs_rowwave,"
            f"{speedup:.2f}")
        assert speedup >= 2.0, (
            f"wavefront must deliver >= 2x row-wave pairs/s at B={B}, "
            f"Lq=Lr={L} (got {speedup:.2f}x)")
    return out


def emission_sweep(csv=print, *, n: int, reps: int = 10,
                   join_impl: str = "all",
                   max_pairs: int = 1 << 14, seed: int = 42) -> dict:
    """Candidate-emission microbenchmark at the FIXED acceptance corpus
    (:data:`EMISSION_N` planted-family sequences — the corpus ``run()``
    uses at full size): warmed steady-state self-join wall time per
    ``join_impl``. ``max_pairs`` is deliberately a typical *starting*
    capacity well below the true pair count, so the legacy orchestration
    pays its documented grow-and-retry cost — eliminating that retry (and
    the host merge) is exactly what the fused SpGEMM join is for. The
    >= 2x criterion is asserted whenever both impls run, after checking
    their pair arrays are bit-identical."""
    n_fam = EMISSION_N // 8
    corpus = make_family_corpus(FamilyCorpusConfig(
        n_families=n_fam, family_size=4,
        n_singletons=EMISSION_N - 4 * n_fam,
        len_mean=150, len_std=25, sub_rate=0.03, seed=seed))
    cfg = LSHConfig(k=3, T=13, f=32, d=1)
    index = SignatureIndex.build(cfg, corpus["ids"], corpus["lens"])
    index._ensure_built()
    out = {"n_seqs": EMISSION_N, "max_pairs": max_pairs, "reps": reps}
    impls = [i for i in ("legacy", "spgemm") if join_impl in ("all", i)]
    pairs_ref = None
    for impl in impls:
        for _ in range(2):                              # warm both programs
            join = lsh_self_join(index, max_pairs=max_pairs, join_impl=impl)
        if pairs_ref is None:
            pairs_ref = join.pairs
        else:
            np.testing.assert_array_equal(pairs_ref, join.pairs)
        t0 = time.perf_counter()
        for _ in range(reps):
            join = lsh_self_join(index, max_pairs=max_pairs, join_impl=impl)
        dt = (time.perf_counter() - t0) / reps
        out[impl] = {"join_ms": round(dt * 1e3, 3),
                     "cands_per_sec": round(join.n_candidates / dt, 1)}
        csv(f"allpairs,{n},emission_{impl},join_ms,{dt * 1e3:.3f}")
        csv(f"allpairs,{n},emission_{impl},cands_per_sec,"
            f"{join.n_candidates / dt:.0f}")
    out["candidates"] = int(join.n_candidates)
    if "legacy" in out and "spgemm" in out:
        speedup = out["legacy"]["join_ms"] / out["spgemm"]["join_ms"]
        out["speedup_spgemm_vs_legacy"] = round(speedup, 2)
        out["bitexact_vs_legacy"] = True
        csv(f"allpairs,{n},emission_spgemm,speedup_vs_legacy,{speedup:.2f}")
        assert speedup >= 2.0, (
            f"fused SpGEMM emission must beat the legacy orchestration "
            f">= 2x at the n={EMISSION_N} acceptance corpus "
            f"(got {speedup:.2f}x)")
    return out


def _span_split(ids, lens, pairs, wc):
    """Seconds of ``score_pairs``' host-gather, wave (by kind) and drain
    spans, sorted by name. With ``wc.profile`` each wave blocks inside its
    span, so the wave spans hold the device time."""
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
        elif sp["name"] in ("host_gather", "drain"):
            key = sp["name"]
        else:
            continue
        split[key] = split.get(key, 0.0) + sp["dur"]
    TRACER.clear()
    return sorted(split.items())


def run(csv=print, n_seqs: int = 2048, naive_sample: int = 192,
        use_pallas: bool = False, profile: bool = False,
        json_path: str | None = None, dp_kernel: str = "all",
        gap_mode: str = "all", join_impl: str = "all"):
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

    # ---- PR 2 pipeline: host gather, synchronous, no prefilter -----------
    # pinned bool, not None/auto: PR 2's default was use_pallas=False, and
    # the baseline must stay PR 2 behavior even on a TPU backend
    pr2 = dataclasses.replace(PR2_WAVE, use_pallas=bool(use_pallas))
    _warm(ids, lens, join.pairs, pr2)
    t0 = time.time()
    s_pr2 = score_pairs(ids, lens, join.pairs, pr2)
    t_pr2 = time.time() - t0
    csv(f"allpairs,{n},pr2,score_s,{t_pr2:.3f}")
    csv(f"allpairs,{n},pr2,waves,{s_pr2.n_waves}")
    csv(f"allpairs,{n},pr2,pairs_per_sec,{join.n_candidates / t_pr2:.0f}")

    # ---- device-resident pipeline: fused gather + prefilter + ring -------
    devw = dataclasses.replace(DEVICE_WAVE, use_pallas=use_pallas or None)
    _warm(ids, lens, join.pairs, devw)
    t0 = time.time()
    s_dev = score_pairs(ids, lens, join.pairs, devw)
    t_dev = time.time() - t0
    reject_rate = s_dev.n_prefiltered / max(join.n_candidates, 1)
    csv(f"allpairs,{n},device,score_s,{t_dev:.3f}")
    csv(f"allpairs,{n},device,waves,{s_dev.n_waves}")
    csv(f"allpairs,{n},device,wave_shapes,{s_dev.n_shapes}")
    csv(f"allpairs,{n},device,pairs_per_sec,{join.n_candidates / t_dev:.0f}")
    csv(f"allpairs,{n},device,prefilter_reject_rate,{reject_rate:.4f}")

    # survivors bit-exact with the PR 2 path
    np.testing.assert_array_equal(s_dev.scores[s_dev.kept],
                                  s_pr2.scores[s_dev.kept])
    csv(f"allpairs,{n},device,survivor_bitexact,1")

    # prefilter recall at the family score threshold
    high = s_pr2.scores >= FAMILY_SCORE_T
    recall = float(s_dev.kept[high].mean()) if high.any() else 1.0
    csv(f"allpairs,{n},device,recall_at_S{FAMILY_SCORE_T},{recall:.4f}")
    assert recall >= 0.99, (
        f"X-drop prefilter lost {(1 - recall):.1%} of pairs with SW score "
        f">= {FAMILY_SCORE_T} (need >= 99% recall)")

    speedup_score = t_pr2 / t_dev
    t_e2e_pr2 = t_build + t_join + t_pr2
    t_e2e_dev = t_build + t_join + t_dev
    speedup_e2e = t_e2e_pr2 / t_e2e_dev
    csv(f"allpairs,{n},device,speedup_score_vs_pr2,{speedup_score:.2f}")
    csv(f"allpairs,{n},device,speedup_e2e_vs_pr2,{speedup_e2e:.2f}")
    if n >= 2048:
        assert speedup_e2e >= 3, (
            f"device-resident pipeline must beat the PR 2 pipeline >= 3x "
            f"end-to-end (got {speedup_e2e:.2f}x)")

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
    wave_sc = score_pairs(ids, lens, check, pr2).scores
    for row, (a, b) in enumerate(check):
        assert wave_sc[row] == sw_score(ids[a][: lens[a]], ids[b][: lens[b]])
    csv(f"allpairs,{n},pr2,wave_score_parity,1")

    # ---- score-phase DP sweep: rowwave vs wavefront, linear vs affine ----
    dp = dp_kernel_sweep(csv, n=n, dp_kernel=dp_kernel, gap_mode=gap_mode)

    # ---- emission-phase sweep: fused SpGEMM join vs legacy orchestration -
    emission = emission_sweep(csv, n=n, join_impl=join_impl)

    # ---- attribution: host-gather vs device-DP split (--profile) ---------
    if profile:
        for name, wc in (("pr2", pr2), ("device", devw)):
            for k, v in _span_split(ids, lens, join.pairs,
                                    dataclasses.replace(wc, profile=True)):
                csv(f"allpairs,{n},{name},profile_{k}_s,{v:.3f}")

    if json_path:
        payload = {
            "bench": "allpairs", "n_seqs": n,
            "candidates": int(join.n_candidates),
            "index_build_s": round(t_build, 3),
            "selfjoin_s": round(t_join, 3),
            "pr2": {"score_s": round(t_pr2, 3), "waves": s_pr2.n_waves,
                    "pairs_per_sec": round(join.n_candidates / t_pr2, 1),
                    "wall_clock_s": round(t_e2e_pr2, 3)},
            "device": {"score_s": round(t_dev, 3), "waves": s_dev.n_waves,
                       "pairs_per_sec": round(join.n_candidates / t_dev, 1),
                       "prefilter_reject_rate": round(reject_rate, 4),
                       "wall_clock_s": round(t_e2e_dev, 3)},
            "speedup": {"score_vs_pr2": round(speedup_score, 2),
                        "e2e_vs_pr2": round(speedup_e2e, 2),
                        "vs_naive_extrapolated": round(speedup_naive, 1)},
            "dp_kernels": dp,
            "emission": emission,
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
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="report host-gather vs device-DP time split")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable summary here")
    ap.add_argument("--dp-kernel", default="all",
                    choices=["all", "rowwave", "wavefront"],
                    help="restrict the score-phase DP sweep")
    ap.add_argument("--gap-mode", default="all",
                    choices=["all", "linear", "affine"],
                    help="restrict the score-phase DP sweep")
    ap.add_argument("--join-impl", default="all",
                    choices=["all", "spgemm", "legacy"],
                    help="restrict the candidate-emission sweep")
    args = ap.parse_args(argv)
    n = args.n_seqs or (256 if args.smoke else 2048)
    sample = 32 if args.smoke else 192
    json_path = args.json or ("BENCH_allpairs.json" if args.smoke else None)
    run(n_seqs=n, naive_sample=sample, use_pallas=args.pallas,
        profile=args.profile, json_path=json_path,
        dp_kernel=args.dp_kernel, gap_mode=args.gap_mode,
        join_impl=args.join_impl)


if __name__ == "__main__":
    main()
