#!/usr/bin/env python3
"""Bring-up smoke: the two user paths on one TPU chip, every result checked.

    python3 chip_smoke.py                # one chip: all-pairs + serving
    python3 chip_smoke.py --four-chips   # four chips: the sharded paths only

One chip, two phases, at dataset sizes from the paper (Tables 5.1/5.2):

* **all-pairs** — an NC_000913-shaped family corpus (4,146 sequences, mean
  length 316: the paper's whole E. coli proteome, uncut) through
  ``all_pairs_search`` with score-only waves and the ungapped prefilter, so
  the Pallas DP and prefilter kernels carry the scoring; once with the
  default band layout and once with the paper's flip layout (which emits
  candidates through ``emit_upper_pairs``). LSH is the paper's §5.3 point
  (k=3, T=13, f=32, d=0). Checked: pair set == brute-force band
  collisions, DP and prefilter scores of 512 sampled kept pairs == the
  host oracles of ``kernels/ref.py``, edges == the planted families'
  candidate pairs.
* **serving** — a Swiss-Prot-shaped reference index (454,401 x mean 373)
  served through ``AsyncEngine`` over a two-replica ``ReplicaFleet`` in
  probe mode, plus one dense batch through the Pallas Hamming kernel.
  Checked: every future ``Completed``, async == synchronous ``flush()``
  bit for bit, dense top-k == a numpy top-k over ``hamming_dist_ref``,
  every reference within Hamming d of a query in its probe answer.

``--four-chips`` runs only what exists across chips, against one shard:
the ``ShardedIndex`` ring probe over four devices, and
``lsh_self_join(n_shards=4)``. It prints the device holding each shard.

Each phase prints its route (Pallas kernel or jnp) and wall-clock. Any
failed check, non-``Completed`` future or crashed worker exits non-zero;
so does a run that finds no TPU, which prints no result line. The last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.util import use_compile_cache  # noqa: E402

# the paper's datasets (configs/scallops.py::DATASETS)
ECOLI_N, ECOLI_LEN = 4_146, 316
SWISSPROT_N, SWISSPROT_LEN = 454_401, 373
N_SAMPLE = 512          # kept pairs checked against the host DP oracles


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


class Clock:
    """Wall-clock per phase step, printed as it ends."""

    def __init__(self, phase: str):
        self.phase = phase
        self.t = time.perf_counter()

    def lap(self, what: str) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        print(f"[{self.phase}] {what}: {dt:.3f} s", flush=True)
        return dt


# ------------------------------------------------------------------ all-pairs
def family_corpus(seed: int, n: int = ECOLI_N, len_mean: int = ECOLI_LEN):
    """Planted families of 4 (one eighth of the corpus each way) plus
    unrelated singletons; within-family identity ~97%."""
    from repro.data import FamilyCorpusConfig, make_family_corpus
    n_fam = n // 8
    return make_family_corpus(FamilyCorpusConfig(
        n_families=n_fam, family_size=4, n_singletons=n - 4 * n_fam,
        len_mean=len_mean, len_std=80, sub_rate=0.03, seed=seed))


def hamming_pairs(sigs: np.ndarray, pairs: set, d: int) -> set:
    """The subset of ``pairs`` within Hamming ``d`` (host popcount)."""
    if not pairs:
        return set()
    p = np.array(sorted(pairs), np.int64)
    dist = np.bitwise_count(sigs[p[:, 0]] ^ sigs[p[:, 1]]).sum(axis=1)
    return set(map(tuple, p[dist <= d].tolist()))


def check_dp_sample(ids, lens, pairs, scored, x, rng, n: int = N_SAMPLE):
    """Scores of ``n`` sampled prefilter survivors against the host Gotoh
    oracle (linear gaps: open == extend == GAP) and the ungapped X-drop
    oracle."""
    from repro.align.smith_waterman import GAP
    from repro.kernels.ref import sw_affine_ref, ungapped_xdrop_ref
    kept = np.flatnonzero(scored.kept)
    check(len(kept) >= n, f"{len(kept)} kept pairs >= {n} to sample")
    bad_sw = bad_ung = 0
    for p in rng.choice(kept, size=n, replace=False):
        i, j = pairs[p]
        q, r = ids[i, :lens[i]], ids[j, :lens[j]]
        bad_sw += int(scored.scores[p] != sw_affine_ref(q, r, GAP, GAP)[0])
        bad_ung += int(scored.ungapped[p]
                       != ungapped_xdrop_ref(q, r, 1 << 30 if x is None
                                             else x))
    check(bad_sw == 0, f"DP scores of {n} sampled kept pairs == "
          f"sw_affine_ref ({bad_sw} differ)")
    check(bad_ung == 0, f"prefilter scores of the same {n} pairs == "
          f"ungapped_xdrop_ref ({bad_ung} differ)")


def check_families(res, truth: np.ndarray) -> None:
    """Edges are exactly the candidate pairs inside one planted family, so
    the families found are the planted ones as far as the join reached."""
    pairs = res.pairs
    same = truth[pairs[:, 0]] == truth[pairs[:, 1]]
    edges = res.families.edge_mask
    check(np.array_equal(edges, same),
          f"edges == planted within-family candidate pairs "
          f"({int(edges.sum())} edges; {int((edges & ~same).sum())} "
          f"cross-family, {int((same & ~edges).sum())} family pairs lost)")
    pure = all(len(set(truth[f].tolist())) == 1 for f in res.families.families)
    check(pure, f"all {res.families.n_families} families found are pure")


def allpairs_phase(seed: int, n: int = ECOLI_N, n_sample: int = N_SAMPLE):
    from repro.allpairs import (AllPairsConfig, WaveConfig, all_pairs_search,
                                brute_force_collisions)
    from repro.configs.scallops import perf_config
    from repro.index import SignatureIndex
    from repro.kernels import ops
    from repro.kernels.sw import on_tpu, resolve_interpret
    from repro.util import next_pow2

    clock = Clock("allpairs")
    corpus = family_corpus(seed, n)
    ids, lens, truth = corpus["ids"], corpus["lens"], corpus["labels"]
    print(f"[allpairs] corpus: {len(lens)} sequences, mean length "
          f"{lens.mean():.1f}, max {lens.max()} ({len(lens)} of "
          f"{ECOLI_N} NC_000913-shaped)", flush=True)
    lsh = perf_config()
    cfg = AllPairsConfig(lsh=lsh, min_score=150,
                         wave=WaveConfig(with_pid=False, prefilter=True))
    pallas = cfg.wave.use_pallas if cfg.wave.use_pallas is not None \
        else on_tpu()
    print(f"[allpairs] route: DP waves + prefilter -> "
          f"{'pallas' if pallas else 'jnp'} (interpret="
          f"{resolve_interpret(cfg.wave.pallas_interpret)})", flush=True)
    clock.lap("corpus")

    results = {}
    for layout in ("band", "flip"):
        index = SignatureIndex.build(lsh, ids, lens, layout=layout)
        index._ensure_built()
        clock.lap(f"{layout}: index build")
        part = index.partition(1)
        _, offs, slab_ids = part.host_slabs()
        if layout == "flip":
            cap = next_pow2(int(part.pair_totals.max()))
            route = ops.emission_route(offs.shape[-1], slab_ids.shape[-1],
                                       cap)
        else:
            route = "jnp (the band layout's keyed join has no emission kernel)"
        print(f"[allpairs] {layout}: emission slab offsets {offs.shape}, ids "
              f"{slab_ids.shape} -> {route}", flush=True)
        res = all_pairs_search(ids, lens, cfg, index=index)
        sc = res.scored
        clock.lap(f"{layout}: all_pairs_search ({res.join.n_candidates} "
                  f"pairs, {sc.n_waves} waves over {sc.n_shapes} shapes, "
                  f"{sc.n_prefiltered} prefiltered, "
                  f"{res.families.n_families} families)")
        bf = hamming_pairs(index.sigs, brute_force_collisions(index), lsh.d)
        check(set(map(tuple, res.pairs.tolist())) == bf,
              f"{layout}: pair set == brute-force collisions within "
              f"Hamming {lsh.d} ({len(bf)} pairs)")
        clock.lap(f"{layout}: brute-force check")
        results[layout] = res

    band, flip = results["band"], results["flip"]
    check(np.array_equal(band.pairs, flip.pairs)
          and np.array_equal(band.scored.scores, flip.scored.scores)
          and np.array_equal(band.scored.kept, flip.scored.kept),
          "band and flip layouts give identical pairs, scores, survivors")
    check_dp_sample(ids, lens, band.pairs, band.scored, cfg.wave.xdrop,
                    np.random.default_rng(seed), n_sample)
    clock.lap("oracle check")
    check_families(band, truth)


# ------------------------------------------------------------------- serving
def serving_data(seed: int, n_refs: int = SWISSPROT_N,
                 n_queries: int = 320):
    from repro.data import SyntheticProteinConfig, make_protein_sets
    n_hom = n_queries * 4 // 5
    return make_protein_sets(SyntheticProteinConfig(
        n_refs=n_refs, n_homolog_queries=n_hom,
        n_decoy_queries=n_queries - n_hom, ref_len_mean=SWISSPROT_LEN,
        ref_len_std=80, seed=seed))


def serving_lsh():
    """The serving default (``launch/search_serve.py``): splitmix hash
    bits, exact within Hamming d=1 under the two-band layout."""
    from repro.core import LSHConfig
    return LSHConfig(k=3, T=13, f=32, d=1, scheme="splitmix")


def build_index(data, lsh, clock):
    from repro.index import SignatureIndex
    index = SignatureIndex.build(lsh, data["ref_ids"], data["ref_lens"])
    index._ensure_built()
    clock.lap(f"index build ({index.size} refs, {index.n_bands} bands)")
    return index


def query_sigs(lsh, q_ids, q_lens):
    from repro.core import ScalLoPS
    sl = ScalLoPS(lsh)
    return (np.asarray(sl.signatures(q_ids, q_lens)),
            np.asarray(sl.feature_counts(q_ids, q_lens)) > 0)


def check_probe_answers(index, q_sig, q_ok, got_ids, got_d, d: int, k: int):
    """Every valid reference within Hamming ``d`` of a query is in its
    answer: the answer's entries at distance <= d are exactly the first
    min(k, |within d|) of them by (distance, id) — the engine's tie-break."""
    ref = index.sigs
    ref_ok = index.valid
    bad = 0
    for qi in range(len(q_sig)):
        if not q_ok[qi]:
            bad += int((got_ids[qi] != -1).any())
            continue
        dist = np.bitwise_count(ref ^ q_sig[qi]).sum(axis=1)
        near = np.flatnonzero((dist <= d) & ref_ok)
        near = near[np.lexsort((near, dist[near]))][:k]
        sel = (got_ids[qi] >= 0) & (got_d[qi] <= d)
        bad += int(not (np.array_equal(got_ids[qi][sel], near)
                        and np.array_equal(got_d[qi][sel], dist[near])))
    check(bad == 0, f"every reference within Hamming {d} of each of "
          f"{len(q_sig)} queries is in its probe answer ({bad} differ)")


def check_dense(index, q_sig, q_ok, nid, nd, k: int):
    """Dense top-k == a numpy top-k over ``hamming_dist_ref`` distances,
    ties to the smaller id, invalid references masked."""
    import jax.numpy as jnp

    from repro.kernels.ref import hamming_dist_ref
    dist = np.asarray(hamming_dist_ref(jnp.asarray(q_sig),
                                       index.device_sigs)).astype(np.int64)
    dist[:, ~index.valid] = 1 << 40
    bad = 0
    for qi in range(len(q_sig)):
        order = np.lexsort((np.arange(dist.shape[1]), dist[qi]))[:k]
        want_d = dist[qi][order]
        want_i = np.where(want_d < 1 << 40, order, -1)
        want_d = np.where(want_d < 1 << 40, want_d, -1)
        if not q_ok[qi]:
            want_i = want_d = np.full(k, -1)
        bad += int(not (np.array_equal(nid[qi], want_i)
                        and np.array_equal(nd[qi], want_d)))
    check(bad == 0, f"dense top-{k} of {len(q_sig)} queries == numpy top-k "
          f"over hamming_dist_ref ({bad} differ)")


def serving_phase(seed: int, n_refs: int = SWISSPROT_N, n_queries: int = 320):
    from repro.index import QueryEngine, ServingConfig
    from repro.kernels.sw import on_tpu
    from repro.serve import AsyncEngine, Completed, ReplicaFleet

    clock = Clock("serving")
    data = serving_data(seed, n_refs, n_queries)
    q_ids, q_lens = data["query_ids"], data["query_lens"]
    print(f"[serving] references: {n_refs} of {SWISSPROT_N} Swiss-Prot-"
          f"shaped, mean length {data['ref_lens'].mean():.1f}; "
          f"{len(q_lens)} queries", flush=True)
    clock.lap("data")
    lsh = serving_lsh()
    index = build_index(data, lsh, clock)
    k = 10
    print("[serving] route: probe -> jnp (bucket searchsorted + gather, "
          "no kernel); dense -> "
          f"{'pallas' if on_tpu() else 'interpret'} hamming_dist_kernel",
          flush=True)

    # ---- async tier: two replicas behind the router, one future per query
    scfg = ServingConfig(k=k, max_batch=64, mode="probe")
    fleet = ReplicaFleet(index, scfg, n_replicas=2)
    eng = AsyncEngine(fleet, max_wait_ms=2.0)
    clock.lap("fleet start")
    futures = [eng.submit(q_ids[i][:q_lens[i]]) for i in range(len(q_lens))]
    results = [f.result(timeout=900) for f in futures]
    clock.lap(f"async serve of {len(results)} queries (compiles included)")
    est, fst = eng.stats(), fleet.stats()
    eng_clean, fleet_clean = eng.close(), fleet.close()
    kinds = {}
    for r in results:
        kinds[type(r).__name__] = kinds.get(type(r).__name__, 0) + 1
    check(all(isinstance(r, Completed) for r in results),
          f"every future Completed ({kinds})")
    crashes = est.get("dispatch", {}).get("crashes", 0) + \
        fst.get("ingest", {}).get("crashes", 0)
    fails = fst["counters"]["replica_failures"]
    check(eng_clean and fleet_clean and crashes == 0 and fails == 0,
          f"no worker crashed or wedged (dispatch+ingest crashes={crashes},"
          f" replica failures={fails})")
    a_ids = np.stack([r.ids for r in results])
    a_d = np.stack([r.dists for r in results])

    # ---- the synchronous engine over the same index
    sync = QueryEngine(index, scfg)
    for i in range(len(q_lens)):
        sync.submit(q_ids[i][:q_lens[i]])
    out = sync.flush()
    clock.lap("synchronous flush()")
    check(np.array_equal(a_ids, np.stack([o[0] for o in out]))
          and np.array_equal(a_d, np.stack([o[1] for o in out])),
          f"async answers == synchronous flush() for {len(out)} queries")

    q_sig, q_ok = query_sigs(lsh, q_ids, q_lens)
    check_probe_answers(index, q_sig, q_ok, a_ids, a_d, lsh.d, k)
    hom = [i for i, (parent, _) in enumerate(data["truth"]) if parent >= 0]
    hits = sum(data["truth"][i][0] in set(a_ids[i].tolist()) for i in hom)
    print(f"[serving] planted parent in the top-{k}: {hits}/{len(hom)} "
          f"homolog queries", flush=True)
    clock.lap("probe oracle check")

    # ---- one dense batch: the Pallas Hamming kernel over the whole index
    dense = QueryEngine(index, ServingConfig(k=k, max_batch=64,
                                             mode="dense"))
    nb = min(64, len(q_lens))
    nid, nd = dense.query_batch(q_ids[:nb], q_lens[:nb])
    clock.lap(f"dense batch of {nb} (compile included)")
    check_dense(index, q_sig[:nb], q_ok[:nb], nid, nd, k)
    clock.lap("dense oracle check")


# ----------------------------------------------------------------- 4 chips
def shard_devices(arr) -> str:
    """Which device holds which rows of a sharded array."""
    return ", ".join(f"rows {s.index[0].start}:{s.index[0].stop} -> "
                     f"{s.device}" for s in arr.addressable_shards)


def four_chip_phase(seed: int, n_refs: int = SWISSPROT_N,
                    n_queries: int = 320, n_seqs: int = ECOLI_N):
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.allpairs import AllPairsConfig, lsh_self_join
    from repro.index import ShardedIndex, SignatureIndex
    from repro.kernels import ops
    from repro.util import next_pow2

    devs = jax.devices()
    check(len(devs) >= 4, f"{len(devs)} devices >= 4")
    mesh4 = Mesh(np.array(devs[:4]), ("data",))
    mesh1 = Mesh(np.array(devs[:1]), ("data",))

    # ---- ShardedIndex ring probe: 4 shards vs 1
    clock = Clock("ring")
    data = serving_data(seed, n_refs, n_queries)
    clock.lap("data")
    lsh = serving_lsh()
    index = build_index(data, lsh, clock)
    q_sig, _ = query_sigs(lsh, data["query_ids"], data["query_lens"])
    s4, s1 = ShardedIndex(index, mesh4), ShardedIndex(index, mesh1)
    clock.lap("placement")
    print(f"[ring] bucket slabs: {shard_devices(s4._slabs[2])}", flush=True)
    check(len({s.device for s in s4._slabs[2].addressable_shards}) == 4,
          "the four shards' slabs sit on four distinct devices")
    a4 = s4.topk(q_sig, k=10)
    a1 = s1.topk(q_sig, k=10)
    clock.lap(f"ring probe of {len(q_sig)} queries, 4 and 1 shards")
    check(all(np.array_equal(x, y) for x, y in zip(a4[:2], a1[:2])),
          f"4-shard ring probe == 1-shard probe for {len(q_sig)} queries")

    # ---- sharded self-join: 4 shards vs 1
    clock = Clock("selfjoin")
    corpus = family_corpus(seed, n_seqs)
    ap = AllPairsConfig().lsh       # the subsystem default: two bands, d=1
    index = SignatureIndex.build(ap, corpus["ids"], corpus["lens"])
    part = index.partition(4)
    _, offs, slab_ids = part.host_slabs()
    cap = next_pow2(int(part.pair_totals.max()))
    placed = jax.device_put(slab_ids, NamedSharding(mesh4, P("data")))
    print(f"[selfjoin] emission slabs offsets {offs.shape}, ids "
          f"{slab_ids.shape}, cap {cap} -> "
          f"{ops.emission_route(offs.shape[-1], slab_ids.shape[-1], cap)}; "
          f"shards: {shard_devices(placed)}", flush=True)
    clock.lap("index build")
    j4 = lsh_self_join(index, d=ap.d, n_shards=4)
    j1 = lsh_self_join(index, d=ap.d, n_shards=1)
    clock.lap(f"self-join, 4 and 1 shards ({j1.n_candidates} pairs)")
    check(np.array_equal(j4.pairs, j1.pairs)
          and np.array_equal(j4.indptr, j1.indptr),
          f"4-shard self-join == 1-shard self-join ({j4.n_candidates} "
          f"pairs, bit-identical)")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 2
    kind, count = devs[0].device_kind, len(devs)
    print(f"[device] {platform} {kind} x {count}; compile cache {cache}",
          flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            allpairs_phase(args.seed)
            serving_phase(args.seed)
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"[total] {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
