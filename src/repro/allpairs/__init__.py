"""repro.allpairs — many-against-many all-pairs similarity search.

The paper's pipeline is one-directional (queries vs. a reference DB); the
dominant metagenomic workload is all-vs-all over a whole corpus (PASTIS,
arXiv:2009.14467; extreme-scale many-against-many, arXiv:2303.01845). This
subsystem computes the corpus similarity graph on top of the persistent LSH
index:

  corpus -> SignatureIndex.build -> LSH self-join (within-bucket pairs,
  deduped, upper-triangular CSR) -> pair scheduler (fixed-shape waves keyed
  by padded length) -> batched Smith-Waterman row-wave scoring (+ PID)
  -> similarity graph -> union-find connected components = protein families

* ``selfjoin`` — :func:`lsh_self_join`: exact band-collision enumeration
  with the grow-and-retry capacity discipline; CSR adjacency output.
* ``tiles``   — :func:`score_pairs`: waves keyed by padded-length shape
  alone, *device-resident* batched SW waves — fused on-device gathers
  (corpus uploaded once, per-wave H2D is just pair indices), an optional
  ungapped X-drop prefilter that skips full DP for hopeless pairs, and
  async double-buffered dispatch drained through a small in-flight ring
  (jnp row-wave or the Pallas tile kernel).
* ``graph``   — :func:`cluster_families`: PID/score-thresholded edges,
  union-find components, families largest-first.

Growth is incremental end to end: :func:`all_pairs_ingest` appends new
sequences to the index (append-only segments), delta-joins only the pairs
touching the new rows (:func:`lsh_delta_join` — resident-vs-resident pairs
are never re-enumerated), scores them through the same wave pipeline, and
unions the surviving edges into a persistent disjoint-set
(:class:`~repro.allpairs.graph.FamilyForest`) — families equal a
from-scratch recluster of the grown corpus.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np

from ..core.pipeline import LSHConfig
from ..index.store import SignatureIndex
from ..obs import span
from .graph import (FamilyForest, FamilyResult, ForestMismatch,
                    cluster_families, families_from_labels, threshold_edges,
                    union_find)
from .selfjoin import (JoinPrefilter, SelfJoinResult,
                       brute_force_collisions, lsh_delta_join, lsh_self_join)
from .tiles import PairScores, WaveConfig, score_pairs, wave_plan


@dataclass(frozen=True)
class AllPairsConfig:
    lsh: LSHConfig = field(default_factory=lambda: LSHConfig(k=3, T=13, f=32,
                                                             d=1))
    bands: int | None = None     # index bands (default: d+1)
    n_shards: int = 1            # bucket shards for the self-join (each
                                 # device emits its own buckets' pairs);
                                 # pair with wave=WaveConfig(n_devices=...)
                                 # for multi-device SW waves
    hamming_filter: bool = True  # exact-filter candidates at Hamming <= d
    wave: WaveConfig = field(default_factory=lambda: WaveConfig(with_pid=True))
    min_pid: float = 50.0        # family edge threshold (percent identity)
    min_score: int = 60          # edge threshold when waves skip PID
    max_pairs: int = 1 << 16     # initial self-join capacity (grows)
    fuse_prefilter: bool = False  # run the ungapped X-drop prefilter INSIDE
                                  # join emission (rejected pairs never reach
                                  # the host; wave.prefilter_min/xdrop supply
                                  # the threshold) — the surviving pair set
                                  # is bit-exact with the unfused wave
                                  # prefilter, which is then skipped
    join_impl: str = "spgemm"    # candidate-generation orchestration:
                                 # "spgemm" (fused device-resident masked
                                 # A^T A) or "legacy" (pre-SpGEMM host-merge
                                 # path, kept one PR) — identical pair arrays


@dataclass(frozen=True)
class AllPairsResult:
    join: SelfJoinResult         # candidate pair set (CSR adjacency)
    scored: PairScores           # SW scores (+ PID) aligned with join.pairs
    families: FamilyResult       # thresholded components
    index: SignatureIndex        # the corpus index (reusable/persistable)

    @property
    def pairs(self) -> np.ndarray:
        return self.join.pairs

    @property
    def labels(self) -> np.ndarray:
        return self.families.labels


def _join_prefilter(cfg: AllPairsConfig, ids, lens):
    """The fused in-join prefilter (and the prefilter-free wave to pair it
    with): thresholds come from the SAME WaveConfig knobs as the unfused
    wave prefilter, so fusing never changes which pairs survive."""
    if not cfg.fuse_prefilter:
        return None, cfg.wave
    pf = JoinPrefilter(ids=ids, lens=lens, min_score=cfg.wave.prefilter_min,
                       x=cfg.wave.xdrop, batch=cfg.wave.prefilter_batch,
                       len_quantum=cfg.wave.len_quantum)
    return pf, replace(cfg.wave, prefilter=False)


def all_pairs_search(ids, lens, cfg: AllPairsConfig | None = None,
                     *, index: SignatureIndex | None = None) -> AllPairsResult:
    """Corpus in, protein families out (the subsystem's one-call driver).

    ``index=`` reuses a prebuilt/loaded :class:`SignatureIndex` over the
    same corpus (the paper's pay-once economics applied to the self-join).
    """
    cfg = cfg or AllPairsConfig()
    ids = np.asarray(ids, np.int8)
    lens = np.asarray(lens, np.int32)
    if index is None:
        index = SignatureIndex.build(cfg.lsh, ids, lens, bands=cfg.bands,
                                     n_shards=cfg.n_shards)
    elif index.size != len(lens):
        raise ValueError(f"index covers {index.size} sequences, corpus has "
                         f"{len(lens)}")
    pf, wave = _join_prefilter(cfg, ids, lens)
    join = lsh_self_join(index, d=cfg.lsh.d if cfg.hamming_filter else None,
                         max_pairs=cfg.max_pairs, n_shards=cfg.n_shards,
                         prefilter=pf, join_impl=cfg.join_impl)
    scored = score_pairs(ids, lens, join.pairs, wave)
    with span("graph", cat="allpairs", pairs=len(join.pairs)):
        if cfg.wave.with_pid:
            families = cluster_families(index.size, join.pairs, scored.pid,
                                        min_pid=cfg.min_pid)
        else:       # score-only waves (e.g. the Pallas kernel path)
            families = cluster_families(index.size, join.pairs, None,
                                        scores=scored.scores,
                                        min_score=cfg.min_score)
    return AllPairsResult(join=join, scored=scored, families=families,
                          index=index)


def _edge_mask(scored: PairScores, cfg: AllPairsConfig, pairs) -> np.ndarray:
    """The one edge-survival rule, shared by batch search and ingest."""
    if cfg.wave.with_pid:
        return threshold_edges(pairs, scored.pid, min_pid=cfg.min_pid)
    return threshold_edges(pairs, None, scores=scored.scores,
                           min_score=cfg.min_score)


def forest_from_result(res: AllPairsResult) -> FamilyForest:
    """Seed a persistent forest from a batch run's surviving edges — the
    handoff point from :func:`all_pairs_search` to incremental ingest."""
    forest = FamilyForest(res.index.size)
    forest.union_edges(res.pairs[res.families.edge_mask])
    return forest


@dataclass(frozen=True)
class IngestResult:
    """One incremental ingest: the delta candidate pairs, their scores, and
    the grown corpus's family labels from the persistent forest."""
    join: SelfJoinResult         # DELTA pairs only (>= 1 row is new)
    scored: PairScores           # aligned with join.pairs
    edge_mask: np.ndarray        # which delta pairs survived the threshold
    labels: np.ndarray           # (N,) labels over the GROWN corpus
    forest: FamilyForest         # the updated persistent disjoint-set

    @property
    def families(self) -> list[np.ndarray]:
        return families_from_labels(self.labels)


def all_pairs_ingest(ids, lens, base_size: int,
                     cfg: AllPairsConfig | None = None, *,
                     index: SignatureIndex,
                     forest: FamilyForest) -> IngestResult:
    """Grow the corpus incrementally: rows ``[base_size:]`` of ``ids/lens``
    are new; everything before is the resident corpus ``index`` and
    ``forest`` already cover.

    Appends the new rows to the index (append-only segment) unless the
    caller already did, delta-joins only the pairs touching new rows,
    scores them through the standard wave pipeline, and unions the
    surviving edges into ``forest``. The resulting labels are EXACTLY what
    a from-scratch :func:`all_pairs_search` over the grown corpus produces
    (asserted in tests/test_lifecycle.py) — at delta cost, the paper's
    "data grows faster than compute" economics applied to clustering.
    """
    cfg = cfg or AllPairsConfig()
    ids = np.asarray(ids, np.int8)
    lens = np.asarray(lens, np.int32)
    # validate BEFORE mutating: a stale forest must not leave the index
    # grown (and out of sync with the caller's labels) on the error path
    if forest.n not in (base_size, len(lens)):
        raise ValueError(f"forest covers {forest.n} nodes; expected "
                         f"{base_size} or {len(lens)}")
    if index.size == base_size:
        index.add(ids[base_size:], lens[base_size:])
    elif index.size != len(lens):
        raise ValueError(
            f"index covers {index.size} sequences; expected the resident "
            f"{base_size} (add() pending) or the grown {len(lens)}")
    pf, wave = _join_prefilter(cfg, ids, lens)
    join = lsh_delta_join(index, base_size=base_size,
                          d=cfg.lsh.d if cfg.hamming_filter else None,
                          max_pairs=cfg.max_pairs, n_shards=cfg.n_shards,
                          prefilter=pf, join_impl=cfg.join_impl)
    scored = score_pairs(ids, lens, join.pairs, wave)
    mask = _edge_mask(scored, cfg, join.pairs)
    forest.grow(index.size)
    forest.union_edges(join.pairs[mask])
    return IngestResult(join=join, scored=scored, edge_mask=mask,
                        labels=forest.labels(), forest=forest)


__all__ = [
    "AllPairsConfig", "AllPairsResult", "all_pairs_search",
    "IngestResult", "all_pairs_ingest", "forest_from_result",
    "SelfJoinResult", "JoinPrefilter", "lsh_self_join", "lsh_delta_join",
    "brute_force_collisions",
    "WaveConfig", "PairScores", "score_pairs", "wave_plan",
    "FamilyResult", "FamilyForest", "cluster_families", "threshold_edges",
    "families_from_labels", "union_find",
]
