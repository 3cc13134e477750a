"""Batched query serving over a :class:`~repro.index.store.SignatureIndex`.

The serving path (paper §5.3's "database prepared once" made operational):

  submit -> micro-batch queue -> pad to a fixed shape ladder (jit-cache
  stability) -> signature generation -> bucket probe (CSR searchsorted) ->
  exact Hamming filter -> fixed-capacity top-k -> optional Smith-Waterman
  re-rank of the top-k.

Two exact-filter paths:

* ``dense`` — the Pallas ``hamming_dist_kernel`` sweeps the query batch
  against the whole index (:func:`repro.kernels.ops.all_pairs_hamming`);
  right when the index fits the arithmetic-intensity window.
* ``probe`` — CSR bucket probing generates candidates; only candidate
  signatures are gathered and popcount-filtered. Right at scale.

Capacity discipline (DESIGN.md §5 "no silent caps"): the probe reports
overflow when a bucket exceeds the candidate cap and the engine grows the
cap and retries; the pair-dump path (:meth:`QueryEngine.search_pairs`) uses
the ``overflowed`` flag of :class:`~repro.core.pipeline.SearchResult` the
same way.
"""
from __future__ import annotations

import functools
import itertools
import time
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alphabet import PAD, encode
from ..core.hamming import hamming_distance
from ..core.pipeline import ScalLoPS
from ..kernels import ops
from ..obs import REGISTRY, Histogram, span, trace_sentinel
from ..obs.trace import record as record_span
from .spgemm import row_product_positions
from .store import SignatureIndex

BIG = 1 << 30  # sentinel distance for masked slots (int32-safe)


# ---------------------------------------------------------------- primitives
# The searchsorted core of every bucket probe is the row slice of the
# SpGEMM candidate product (repro.index.spgemm): a query's product row IS
# the matched bucket's member window. Shared by the id-returning probe
# below and the sharded ring's sig-gathering probe (repro.index.shard),
# so probe and join semantics can never diverge.
_probe_csr_positions = row_product_positions


@functools.partial(jax.jit, static_argnames=("cap",))
@trace_sentinel("probe_csr")
def _probe_csr(qkeys, csr_keys, csr_offsets, csr_ids, *, cap: int):
    """One band's bucket probe: searchsorted into the CSR unique keys.

    qkeys (B,) uint32 -> (cand (B, cap) int32 with -1 padding,
    bucket_size (B,) int32 — the *true* matched-bucket size, which may
    exceed cap; the caller detects truncation from it).
    """
    B = qkeys.shape[0]
    U = csr_keys.shape[0]
    E = csr_ids.shape[0]
    if U == 0 or E == 0:
        return (jnp.full((B, cap), -1, jnp.int32), jnp.zeros(B, jnp.int32))
    idx, ok, size = _probe_csr_positions(qkeys, csr_keys, csr_offsets,
                                         cap=cap, E=E)
    cand = jnp.where(ok, csr_ids[idx], -1)
    return cand, size


@functools.partial(jax.jit, static_argnames=("cap",))
@trace_sentinel("probe_fused")
def _probe_csr_fused(qkeys, csr_keys, csr_offsets, csr_ids, *, cap: int):
    """All bands' bucket probes + cross-band dedup in ONE jitted program.

    The per-band CSR arrays are stacked and padded to common sizes by the
    bucket partition layer (keys padded by repeating the last key, offsets
    by repeating the end offset — padded entries are empty buckets, so they
    match nothing; see repro.index.partition). Fusing removes the per-band
    Python dispatch loop from the probe hot path — one device program per
    query batch instead of n_bands (ROADMAP "probe path on-device").

    qkeys (nb, B) uint32, csr_keys (nb, U), csr_offsets (nb, U+1),
    csr_ids (nb, E) -> (cand (B, nb*cap) int32 with -1 padding, duplicates
    across bands allowed — _topk_from_candidates dedups downstream,
    bucket_size (nb, B) int32 — true matched-bucket sizes).
    """
    def one_band(qk, keys, offsets, ids):
        return _probe_csr(qk, keys, offsets, ids, cap=cap)

    cand, size = jax.vmap(one_band)(qkeys, csr_keys, csr_offsets, csr_ids)
    B = qkeys.shape[1]
    return jnp.transpose(cand, (1, 0, 2)).reshape(B, -1), size


def _dedup_candidates(cand, dist, ok):
    """Row-wise candidate dedup: sort slots by candidate id (invalid ids
    last), mask repeated ids (duplicates carry the same exact distance, so
    keeping the first is lossless). Returns (ids_sorted (B, C),
    dvals (B, C) with BIG in masked slots). Sorting by id makes the
    downstream ``top_k`` break distance ties toward the smaller id — the
    ONE tie-break rule shared by the single-device probe and the sharded
    ring merge (repro.index.shard), which is what makes them bit-exact."""
    B = cand.shape[0]
    sort_key = jnp.where(ok, cand, jnp.int32(2**31 - 1))
    order = jnp.argsort(sort_key, axis=1)
    cs = jnp.take_along_axis(cand, order, axis=1)
    ds = jnp.take_along_axis(dist, order, axis=1)
    oks = jnp.take_along_axis(ok, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((B, 1), bool), cs[:, 1:] == cs[:, :-1]], axis=1)
    oks = oks & ~dup
    return cs, jnp.where(oks, ds, BIG)


@functools.partial(jax.jit, static_argnames=("k",))
@trace_sentinel("topk_candidates")
def _topk_from_candidates(q_sigs, cand, ref_sigs, ref_valid, *, k: int):
    """Exact-filter candidates and keep the k nearest per query.

    cand (B, C) int32 with -1 padding (duplicates across bands allowed —
    deduplicated here). Returns (ids (B, k) int32 with -1 padding,
    dists (B, k) int32 with -1 padding).
    """
    safe = jnp.maximum(cand, 0)
    dist = hamming_distance(q_sigs[:, None, :], ref_sigs[safe])   # (B, C)
    ok = (cand >= 0) & ref_valid[safe]
    cs, dvals = _dedup_candidates(cand, dist, ok)
    return _finalize_topk(dvals, cs, k)


def _finalize_topk(dvals, id_source, k: int):
    """Shared top-k tail: (B, C) distances (BIG = masked) + per-slot ids ->
    ((B, k) ids, (B, k) dists), -1-padded past the valid entries.
    ``id_source=None`` means slot index == reference id (dense path)."""
    C = dvals.shape[1]
    kk = min(k, C)
    neg, idx = jax.lax.top_k(-dvals, kk)
    nd = -neg
    nid = (idx.astype(jnp.int32) if id_source is None
           else jnp.take_along_axis(id_source, idx, axis=1))
    nid = jnp.where(nd < BIG, nid, -1)
    nd = jnp.where(nd < BIG, nd, -1)
    if kk < k:
        pad = ((0, 0), (0, k - kk))
        nid = jnp.pad(nid, pad, constant_values=-1)
        nd = jnp.pad(nd, pad, constant_values=-1)
    return nid, nd


@functools.partial(jax.jit, static_argnames=("k",))
@trace_sentinel("topk_dense")
def _topk_from_dists(dist, ref_valid, *, k: int):
    """(B, N) distances -> top-k (ids, dists) with invalid refs masked."""
    dvals = jnp.where(ref_valid[None, :], dist, BIG)
    return _finalize_topk(dvals, None, k)


def topk_dense(index: SignatureIndex, q_sigs, *, k: int):
    """Exact top-k via the Pallas all-pairs Hamming kernel (whole index)."""
    dist = ops.all_pairs_hamming(jnp.asarray(q_sigs), index.device_sigs)
    return _topk_from_dists(dist, index.device_valid, k=k)


def topk_probe(index: SignatureIndex, q_sigs, *, k: int, cap: int,
               max_cap: int = 1 << 14):
    """Top-k via bucket probing, growing the candidate cap on overflow.

    Returns (ids, dists, final_cap, truncated). Exact within the layout's
    guarantee — every reference within Hamming d of the query shares a
    bucket, so the top-k among candidates contains all true neighbors
    within d — *unless* ``truncated`` is True: a bucket exceeded ``max_cap``
    and candidates were dropped (no silent caps: the flag makes it
    observable; raise ``max_cap`` to restore exactness).
    """
    q_sigs = jnp.asarray(q_sigs)
    while True:
        cand, overflowed = index.probe(q_sigs, cap=cap)
        if not bool(overflowed) or cap >= max_cap:
            break
        cap = min(cap * 2, max_cap)     # grow-and-retry
    ids, dists = _topk_from_candidates(
        q_sigs, cand, index.device_sigs, index.device_valid, k=k)
    return ids, dists, cap, bool(overflowed)


# ---------------------------------------------------------------- serving
@dataclass
class ServingConfig:
    k: int = 10
    max_batch: int = 64
    batch_ladder: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    len_quantum: int = 64           # pad query length to multiples of this
    probe_cap: int = 32             # initial candidates per band per query
    max_probe_cap: int = 1 << 14
    dense_threshold: int = 1024     # "auto": dense kernel below this size
    mode: str = "auto"              # "probe" | "dense" | "auto"
    rerank: bool = False            # Smith-Waterman re-rank of the top-k
    gap_mode: str = "linear"        # re-rank gap model: "linear" |
                                    # "affine" (Gotoh)
    gap_open: int | None = None     # affine defaults: BLOSUM62 -11 / -1
    gap_extend: int | None = None


_STAGES = ("ladder", "sig", "probe", "rerank")

# registry families every engine registers into (children labeled by the
# engine's name, so per-replica streams stay attributable AND merge — the
# fleet-wide latency histogram is the exact fold of the children)
_M_BATCH = REGISTRY.histogram(
    "serve_batch_seconds", "query_batch wall-clock", labelnames=("engine",))
_M_STAGE = REGISTRY.histogram(
    "serve_stage_seconds", "per-batch serving-stage wall-clock "
    "(ladder/sig/probe/rerank)", labelnames=("engine", "stage"))
_M_QUERIES = REGISTRY.counter(
    "serve_queries", "queries served", labelnames=("engine",))
_M_TRUNC = REGISTRY.counter(
    "serve_truncations", "batches whose probe overflowed even at "
    "max_probe_cap (the no-silent-caps counter)", labelnames=("engine",))

_engine_ids = itertools.count()


class _Stats:
    """Bounded per-engine serving stats. The first cut kept EVERY batch
    latency in a growing list — flagged wrong for always-on serving in
    serve/metrics.py's own docstring — and its percentiles couldn't merge
    across replicas. Fixed-log-bucket histograms fix both: O(buckets)
    memory forever, and the registry children (labeled by engine name)
    fold exactly across replicas (repro.obs.registry). Each batch is
    observed twice — into the resettable ``stats()`` view here and into
    the monotonic registry children (reset() must not rewind a scrape)."""

    def __init__(self, name: str):
        self._m_lat = _M_BATCH.labels(engine=name)
        self._m_stage = {s: _M_STAGE.labels(engine=name, stage=s)
                         for s in _STAGES}
        self._m_queries = _M_QUERIES.labels(engine=name)
        self._m_trunc = _M_TRUNC.labels(engine=name)
        self.reset()

    def reset(self) -> None:
        self.lat = Histogram(self._m_lat.bounds)
        # accumulated per-stage seconds over every batch served (coarse
        # wall-clock attribution — jax dispatch is async, so work issued in
        # one stage can complete inside the next sync point; the probe
        # stage carries that slack, documented in stats())
        self.stage = dict.fromkeys(_STAGES, 0.0)
        self.n_queries = 0
        self.truncations = 0        # batches whose probe hit max_probe_cap

    def observe_batch(self, n_queries: int, seconds: float,
                      stage_seconds: dict) -> None:
        self.lat.observe(seconds)
        self._m_lat.observe(seconds)
        self.n_queries += n_queries
        self._m_queries.inc(n_queries)
        for s, v in stage_seconds.items():
            self.stage[s] += v
            self._m_stage[s].observe(v)

    def observe_truncation(self) -> None:
        self.truncations += 1
        self._m_trunc.inc()


class QueryEngine:
    """Micro-batched query serving over a built or loaded index.

    ``submit()`` enqueues raw sequences (strings or encoded int8 rows);
    ``flush()`` drains the queue in fixed-shape micro-batches and returns
    per-query results; ``query_batch()`` is the synchronous batch entry.
    ``ref_seqs=(ids, lens)`` enables Smith-Waterman re-ranking.
    """

    def __init__(self, index: SignatureIndex, cfg: ServingConfig | None = None,
                 *, ref_seqs=None, sharded=None, name: str | None = None):
        self.index = index
        self.cfg = cfg or ServingConfig()
        self.sl = ScalLoPS(index.cfg)
        self.ref_seqs = ref_seqs
        self.sharded = sharded          # optional ShardedIndex fan-out path
        self.name = name or f"engine{next(_engine_ids)}"
        self._probe_cap = self.cfg.probe_cap
        self._queue: list[tuple[np.ndarray, int]] = []
        self._stats = _Stats(self.name)
        self._ref_dev = None            # device-resident (ids, lens) for the
                                        # SW re-rank gather (uploaded once)
        if self.cfg.rerank and ref_seqs is None:
            raise ValueError("rerank=True needs ref_seqs=(ref_ids, ref_lens)")
        self._ref_dev_src = None        # the ref_seqs the snapshot mirrors
        if self.cfg.rerank:       # upload once; skipped when never re-ranking
            self._upload_refs()

    def _upload_refs(self) -> None:
        """Mirror ``self.ref_seqs`` on device for the re-rank gather. Rebind
        ``engine.ref_seqs`` to refresh (e.g. after ``index.add``); in-place
        mutation of the arrays is not tracked (same contract as the index's
        own signatures, which are computed at build time)."""
        self._ref_dev = (jnp.asarray(np.asarray(self.ref_seqs[0], np.int8)),
                         jnp.asarray(np.asarray(self.ref_seqs[1], np.int32)))
        self._ref_dev_src = self.ref_seqs

    # ------------------------------------------------------------ queue
    def submit(self, seq) -> None:
        """Enqueue one query (amino-acid string or encoded int8 array)."""
        if isinstance(seq, str):
            row = np.asarray(encode(seq), np.int8)
        else:
            row = np.asarray(seq, np.int8).reshape(-1)
        self._queue.append((row, len(row)))

    def pending(self) -> int:
        return len(self._queue)

    def flush(self):
        """Serve every queued query; returns [(ids (k,), dists (k,)), ...]
        in submission order."""
        out = []
        queue, self._queue = self._queue, []
        for i in range(0, len(queue), self.cfg.max_batch):
            chunk = queue[i:i + self.cfg.max_batch]
            L = max(l for _, l in chunk)
            ids = np.full((len(chunk), max(L, 1)), PAD, np.int8)
            lens = np.zeros(len(chunk), np.int32)
            for j, (row, l) in enumerate(chunk):
                ids[j, :l] = row
                lens[j] = l
            nid, nd = self.query_batch(ids, lens)
            out.extend((nid[j], nd[j]) for j in range(len(chunk)))
        return out

    # ------------------------------------------------------------ shaping
    def _pad_shapes(self, ids, lens):
        """Pad batch and length to the fixed-shape ladder (jit stability)."""
        B0, L0 = ids.shape
        ladder = [b for b in self.cfg.batch_ladder if b >= B0]
        B = min(ladder) if ladder else self.cfg.max_batch
        q = self.cfg.len_quantum
        L = max(q, -(-L0 // q) * q)
        out = np.full((B, L), PAD, np.int8)
        out[:B0, :L0] = ids
        olens = np.zeros(B, np.int32)
        olens[:B0] = lens
        return out, olens

    # ------------------------------------------------------------ serving
    def query_batch(self, ids, lens):
        """Serve one batch: (B0, L) int8 + (B0,) lengths ->
        (neighbor_ids (B0, k), neighbor_dists (B0, k)) int32 numpy, -1 padded.
        Queries with zero neighbour features (paper §5.2) get all -1."""
        ids = np.asarray(ids, np.int8)
        lens = np.asarray(lens, np.int32)
        B0 = ids.shape[0]
        if B0 > self.cfg.max_batch:
            parts = [self.query_batch(ids[i:i + self.cfg.max_batch],
                                      lens[i:i + self.cfg.max_batch])
                     for i in range(0, B0, self.cfg.max_batch)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))

        t0 = time.perf_counter()
        pids, plens = self._pad_shapes(ids, lens)
        t_ladder = time.perf_counter()
        q_sigs = self.sl.signatures(pids, plens)
        q_valid = np.asarray(self.sl.feature_counts(pids, plens)) > 0
        t_sig = time.perf_counter()

        k = self.cfg.k
        truncated = False
        if self.sharded is not None:
            nid, nd, self._probe_cap, truncated = self.sharded.topk(
                q_sigs, k=k, cap=self._probe_cap,
                max_cap=self.cfg.max_probe_cap)
        elif self._mode() == "dense":
            nid, nd = topk_dense(self.index, q_sigs, k=k)
        else:
            nid, nd, self._probe_cap, truncated = topk_probe(
                self.index, q_sigs, k=k, cap=self._probe_cap,
                max_cap=self.cfg.max_probe_cap)
        if truncated:
            self._stats.observe_truncation()
            warnings.warn(
                f"probe candidates truncated at max_probe_cap="
                f"{self.cfg.max_probe_cap}; top-k may miss neighbors — "
                f"raise ServingConfig.max_probe_cap", RuntimeWarning,
                stacklevel=2)
        nid = np.array(nid)     # writable host copies
        nd = np.array(nd)
        t_probe = time.perf_counter()
        nid[~q_valid] = -1
        nd[~q_valid] = -1
        nid, nd = nid[:B0], nd[:B0]
        if self.cfg.rerank:
            nid, nd = self._rerank(ids, lens, nid, nd)

        t_end = time.perf_counter()
        # spans from the timestamps already taken (no extra clock reads);
        # the enclosing dispatch/route context tags them with the batch's
        # query trace IDs (repro.obs.trace)
        record_span("query_batch", t0, t_end, engine=self.name, B=B0)
        record_span("ladder", t0, t_ladder)
        record_span("sig", t_ladder, t_sig)
        record_span("probe", t_sig, t_probe, cap=self._probe_cap,
                    sharded=self.sharded is not None)
        if self.cfg.rerank:
            record_span("rerank", t_probe, t_end)
        self._stats.observe_batch(B0, t_end - t0, {
            "ladder": t_ladder - t0, "sig": t_sig - t_ladder,
            "probe": t_probe - t_sig, "rerank": t_end - t_probe})
        return nid, nd

    def _mode(self) -> str:
        if self.cfg.mode != "auto":
            return self.cfg.mode
        return "dense" if self.index.size <= self.cfg.dense_threshold \
            else "probe"

    # ------------------------------------------------------------ pair dump
    def search_pairs(self, q_ids, q_lens, *, max_pairs: int | None = None,
                     max_grow: int = 1 << 22):
        """Classic unordered pair dump (`ScalLoPS.search` semantics) against
        the indexed references, honouring the result's ``overflowed`` flag:
        capacity grows and the join retries until nothing is truncated."""
        q_sigs = self.sl.signatures(np.asarray(q_ids, np.int8),
                                    np.asarray(q_lens, np.int32))
        q_valid = np.asarray(self.sl.feature_counts(q_ids, q_lens)) > 0
        mp = max_pairs or self.index.cfg.max_pairs
        while True:
            res = self.sl.search(q_sigs, self.index.device_sigs,
                                 max_pairs=mp, q_valid=q_valid,
                                 r_valid=self.index.device_valid)
            if not bool(res.overflowed) or mp >= max_grow:
                return res
            mp = min(mp * 2, max_grow)  # grow-and-retry

    # ------------------------------------------------------------ rerank
    def _rerank(self, ids, lens, nid, nd):
        """Reorder each query's top-k by Smith-Waterman score (descending).

        Device-resident: the reference corpus was uploaded once at engine
        construction; both pair sides are gathered *on device* inside one
        jitted gather+DP program (`align.smith_waterman.sw_gather_scores`) —
        the only H2D traffic per call is the query batch and the (M,) index
        vectors, never a per-pair host copy loop. The (query, slot) pair
        list is padded to a fixed M (all-PAD rows score 0) and the query
        length is quantized to the serving padding ladder
        (``len_quantum``), so the gather+DP program compiles once per
        ladder rung instead of once per raw batch width.
        """
        from ..align.smith_waterman import sw_gather_scores
        if self.ref_seqs is not self._ref_dev_src:
            self._upload_refs()     # caller rebound ref_seqs (index.add etc.)
        ref_ids_dev, ref_lens_dev = self._ref_dev
        B, K = nid.shape
        qi, ki = np.nonzero(nid >= 0)
        if len(qi) == 0:
            return nid, nd
        rid = nid[qi, ki]
        if rid.max(initial=-1) >= ref_ids_dev.shape[0]:
            # the on-device gather clamps instead of raising — fail loudly
            # rather than silently re-rank against the wrong reference
            raise IndexError(
                f"re-rank hit reference id {int(rid.max())} outside "
                f"ref_seqs ({int(ref_ids_dev.shape[0])} rows); pass the "
                f"grown corpus as ref_seqs after index.add()")
        M = -(-len(qi) // 64) * 64          # fixed-shape ladder for the wave
        qv = np.full(M, -1, np.int32)
        rv = np.full(M, -1, np.int32)
        qv[:len(qi)] = qi
        rv[:len(qi)] = rid
        # quantize Lq to the serving ladder (raw batch widths would retrace
        # the gather+DP program on every new width)
        q = self.cfg.len_quantum
        Lq = max(q, -(-ids.shape[1] // q) * q)
        ids_q = np.full((ids.shape[0], Lq), PAD, np.int8)
        ids_q[:, :ids.shape[1]] = ids
        scores = np.asarray(sw_gather_scores(
            jnp.asarray(ids_q),
            jnp.asarray(np.asarray(lens, np.int32)),
            ref_ids_dev, ref_lens_dev, qv, rv,
            Lq=Lq, Lr=int(ref_ids_dev.shape[1]),
            gap_mode=self.cfg.gap_mode, gap_open=self.cfg.gap_open,
            gap_extend=self.cfg.gap_extend))[:len(qi)]
        smat = np.full((B, K), -np.inf)
        smat[qi, ki] = scores
        order = np.argsort(-smat, axis=1, kind="stable")
        return (np.take_along_axis(nid, order, axis=1),
                np.take_along_axis(nd, order, axis=1))

    # ------------------------------------------------------------ warmup
    def warmup(self, q_ids=None, q_lens=None, *,
               max_len: int | None = None) -> int:
        """Compile every (batch-rung, length-quantum) serving shape before
        traffic arrives, so the open-loop points of an SLO sweep measure
        serving instead of XLA compiles (the jit cache keys on the padded
        ARRAY width — warming only full-width rows silently leaves real
        quanta cold, which the SLO benchmark learned the hard way).

        With sample queries ``(q_ids, q_lens)``, first runs EVERY sample
        through the engine to settle the grow-and-retry probe cap — the
        worst *bucket* in the sample set decides the cap, not the longest
        row, and a cap grown after warmup would retrace every rung
        mid-traffic — then warms exactly the length quanta the samples
        occupy at the settled cap. Without samples, synthesizes rows for
        every quantum up to ``max_len`` (default: one quantum). Emits one
        ``warmup`` span per (rung, quantum); returns shapes warmed. Runs
        through ``query_batch``, so call :meth:`reset_stats` afterwards
        if warmup batches must not pollute serving stats."""
        quanta: dict[int, np.ndarray] = {}
        qm = self.cfg.len_quantum
        if q_ids is not None:
            lens = np.asarray(q_lens)
            for j, L in enumerate(lens):
                q = int(-(-int(L) // qm) * qm)
                if q not in quanta or int(L) > len(quanta[q]):
                    quanta[q] = np.asarray(q_ids[j][:int(L)], np.int8)
        else:
            top = max(int(max_len or qm), qm)
            for q in range(qm, (-(-top // qm) * qm) + 1, qm):
                quanta[q] = np.zeros(q, np.int8)    # shapes are what compile
        rungs = [b for b in self.cfg.batch_ladder if b <= self.cfg.max_batch]
        if q_ids is not None:
            # cap-settling pass: one chunked sweep over the full sample
            # set so the rung loop below compiles at the FINAL cap
            b = max(rungs)
            lens32 = np.asarray(q_lens, np.int32)
            with span("warmup", rung=b, engine=self.name, settle=True,
                      samples=len(lens32)):
                for i in range(0, len(lens32), b):
                    self.query_batch(q_ids[i:i + b], lens32[i:i + b])
        for b in rungs:
            for q, row in sorted(quanta.items()):
                with span("warmup", rung=b, quantum=q, engine=self.name):
                    self.query_batch(np.repeat(row[None, :], b, axis=0),
                                     np.full(b, len(row), np.int32))
        return len(rungs) * len(quanta)

    def reset_stats(self) -> None:
        """Zero the ``stats()`` view (e.g. after warmup). The registry
        children stay monotonic — a Prometheus scrape never rewinds."""
        self._stats.reset()

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        """Latency/throughput summary over every batch served so far —
        bounded memory (fixed-log-bucket histograms, repro.obs.registry),
        percentiles are bucket-interpolated estimates (<= one bucket's
        relative width off the sample percentile). ``index_epoch`` is the
        backing index's segment counter — it moves when the engine serves
        across a live refresh (``index.add`` landed between batches)
        without the engine being rebuilt. ``stage_ms`` splits the
        accumulated wall-clock by serving stage (ladder/sig/probe/rerank;
        jax dispatch is async, so the probe stage — the device sync point
        — absorbs work issued earlier); ``truncations`` counts batches
        whose probe overflowed even at ``max_probe_cap`` (the
        no-silent-caps counter)."""
        st = self._stats
        lat = st.lat
        stage_ms = {s: v * 1e3 for s, v in st.stage.items()}
        if lat.count == 0:
            return dict(n_queries=0, n_batches=0, qps=0.0,
                        p50_ms=0.0, p95_ms=0.0, p99_ms=0.0, mean_ms=0.0,
                        stage_ms=stage_ms, truncations=0,
                        index_epoch=self.index.epoch)
        return dict(
            n_queries=st.n_queries,
            n_batches=lat.count,
            qps=st.n_queries / lat.sum,
            p50_ms=lat.quantile(0.50) * 1e3,
            p95_ms=lat.quantile(0.95) * 1e3,
            p99_ms=lat.quantile(0.99) * 1e3,
            mean_ms=lat.mean * 1e3,
            stage_ms=stage_ms,
            truncations=st.truncations,
            index_epoch=self.index.epoch,
        )
