"""LSH self-join: the corpus joined against itself via the index's buckets.

The many-against-many candidate generator (PASTIS-style similarity graphs):
instead of probing queries against reference buckets, every bucket of the
:class:`~repro.index.store.SignatureIndex` emits its own within-bucket pairs.
A bucket of m members contributes m*(m-1)/2 unordered pairs; pairs colliding
in several bands are deduplicated; the result is the *exact* set of LSH band
collisions — upper-triangular (i < j), only valid (non-zero-signature)
sequences, identical to brute-force enumeration of per-band key equality.
The pigeonhole guarantee carries over: any pair within Hamming distance d of
each other shares >= 1 band, so filtering candidates by packed Hamming
distance (``d=``) yields the exact d-neighborhood graph.

Candidate emission is the masked SpGEMM primitive of
:mod:`repro.index.spgemm` — each bucket slab is the CSR of a
sequence×bucket incidence matrix ``A``, the self-join is the strict upper
triangle of ``AᵀA``, and the delta join is the ``Aᵀ_delta · A_resident``
cross mask (resident×resident never forms). Two orchestrations share those
products behind ``join_impl=``:

* ``"spgemm"`` (default) — the fused path: per-band products, cross-band
  dedup, the optional exact Hamming filter, and survivor compaction run
  device-resident (one program when per-shard demand is uniform), the
  output capacity is sized at the exact emission total so the dedup can
  never overflow (no grow-and-retry), and the fused prefilter consumes
  the pair buffer in place — the join pays ONE host sync (the count).
* ``"legacy"`` — the pre-SpGEMM orchestration (emission programs → host
  merge → separate dedup under grow-and-retry), kept for one PR as the
  bit-exactness reference; both paths produce IDENTICAL result arrays
  (the dedup output is the sorted unique pair set either way).

Emission runs over the shard-owned bucket slabs of
:class:`~repro.index.partition.BucketPartition` (``mix32(key) % n_shards``
— the MapReduce shuffle): with ``n_shards > 1`` each mesh device emits its
own buckets' pairs in parallel (``shard_map``; a vmap over the shard axis
when the process has fewer devices), and the per-shard buffers are merged
with the cross-shard/cross-band dedup. Buckets are never split across
shards, so the union of per-shard emissions is EXACTLY the single-device
pair set — the result arrays are bit-identical for every ``n_shards``.

Capacity is **skew-bounded**: each shard's emission buffer is sized at its
OWN per-(shard, band) within-bucket pair total (quantized to a power of
two to bound recompiles), so one degenerate bucket inflates one shard's
buffer, not every shard's. Uniform demand keeps the single SPMD
``shard_map`` program (one dispatch, the PR 4 lesson); skewed demand falls
back to per-shard emission with a ragged merge — the downstream dedup
lexsorts, so the pair arrays are identical either way.

Incremental growth joins incrementally too: :func:`lsh_delta_join` emits
only the pairs that touch rows appended after ``base_size`` — each new
segment's within-bucket pairs plus its cross pairs against every resident
segment's matching buckets — and, like the batch join, runs per shard
under the bucket partition (matching keys land on the same shard on both
sides of the cross mask, so the per-shard union is exact). The union of
the old pair set and the delta is EXACTLY the from-scratch self-join over
the grown corpus (any collision either has both rows resident, or its
later row lives in a new segment).

Emission reuses the fixed-capacity buffer discipline of ``core/join.py``
(rows past the count are -1; ``overflowed`` means rows were truncated), and
the legacy orchestration wraps dedup in the same grow-and-retry loop as the
serving layer — no silent caps.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..align.smith_waterman import gather_rows, ungapped_xdrop_scores
from ..core.join import PACKED_KEY_MAX_ID, compact_pairs
from ..index.partition import BucketPartition, pad_slabs_pow2
from ..index.spgemm import (spgemm_cross_slab, spgemm_join_self,
                            spgemm_join_self_keys, spgemm_pack,
                            spgemm_self_slab)
from ..index.store import SignatureIndex
from ..obs import span, trace_sentinel
from ..util import next_pow2

JOIN_IMPLS = ("spgemm", "legacy")


def _check_impl(join_impl: str) -> str:
    if join_impl not in JOIN_IMPLS:
        raise ValueError(f"unknown join_impl {join_impl!r} "
                         f"(expected one of {JOIN_IMPLS})")
    return join_impl


@functools.lru_cache(maxsize=16)
def _default_mesh(n: int, axis_name: str):
    """One mesh per shard count (a fresh Mesh per call would defeat the
    jit cache of every program built on it)."""
    return Mesh(np.array(jax.devices()[:n]), (axis_name,))


@functools.lru_cache(maxsize=64)
def _emit_sharded_cached(devices: tuple, axis_name: str, cap: int):
    """The jitted shard_map emission program, cached by the DEVICE TUPLE —
    never by a Mesh object. Device objects are per-process singletons, so
    two freshly constructed (equal) meshes resolve to the same program;
    keying by Mesh relied on Mesh equality semantics and a fresh Mesh per
    call could silently recompile (the PR 5 regression test pins this)."""
    ax = axis_name
    mesh = Mesh(np.array(devices), (ax,))

    @trace_sentinel("emission_spmd", static_key=(devices, cap))
    def shard_fn(offs, ids):
        return spgemm_self_slab(offs[0], ids[0], cap=cap)

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P(ax), P(ax)), out_specs=P(ax),
        check_vma=False))


def _emit_sharded_fn(mesh, axis_name: str, cap: int):
    """Resolve a mesh to the cached SPMD emission program (identity-stable
    across equal meshes — see :func:`_emit_sharded_cached`)."""
    return _emit_sharded_cached(tuple(mesh.devices.flat), axis_name, cap)


@functools.lru_cache(maxsize=64)
def _emit_cross_sharded_cached(devices: tuple, axis_name: str, cap: int):
    """shard_map program for the per-shard delta×resident cross mask —
    cached by device tuple like :func:`_emit_sharded_cached`."""
    ax = axis_name
    mesh = Mesh(np.array(devices), (ax,))

    @trace_sentinel("delta_cross_spmd", static_key=(devices, cap))
    def shard_fn(dk, do, di, rk, ro, ri):
        return spgemm_cross_slab(dk[0], do[0], di[0], rk[0], ro[0], ri[0],
                                 cap=cap)

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P(ax),) * 6, out_specs=P(ax),
        check_vma=False))


def _shard_caps(part: BucketPartition) -> np.ndarray:
    """(S,) int64 emission capacity per shard: its own max per-(shard,
    band) within-bucket pair total, quantized to the next power of two
    (bounds both recompiles and worst-case over-allocation at 2x true
    demand). Skew-bounding: a degenerate bucket inflates only its owning
    shard's cap."""
    if part.pair_totals.size == 0:
        return np.zeros(part.n_shards, np.int64)
    per_shard = part.pair_totals.max(axis=1)
    return np.array([next_pow2(int(c)) for c in per_shard], np.int64)


def _emit_partition(part: BucketPartition, caps: np.ndarray, mesh,
                    axis_name: str, *, to_host: bool = True):
    """Emit every shard's within-bucket pairs over the partition slabs;
    returns the merged (M, 2) candidate rows (-1 rows allowed — the
    downstream dedup drops them) — numpy when ``to_host`` (the legacy
    orchestration), a device array otherwise (the spgemm pack consumes it
    without a host round-trip).

    Uniform demand (all nonzero shard caps equal): ONE program — the
    ``shard_map`` SPMD emission on a mesh of ``part.n_shards`` devices, or
    a vmap over the shard axis on one device. Skewed demand: per-shard
    emission at each shard's own cap (placed on its owning mesh device
    when a mesh is given) with a ragged merge, so buffer memory follows
    per-shard demand instead of the global max.
    """
    live = caps[caps > 0]
    uniform = live.size == 0 or int(live.min()) == int(live.max())
    if uniform:
        cap = int(caps.max())
        if mesh is not None:
            # host -> owning devices directly (NamedSharding split on the
            # shard axis): device 0 never concentrates the stack, and the
            # emission program's in_specs see their layout w/o resharding
            sharding = NamedSharding(mesh, P(axis_name))
            _, offs_np, ids_np = part.host_slabs()
            offs_s = jax.device_put(offs_np, sharding)
            ids_s = jax.device_put(ids_np, sharding)
            out = _emit_sharded_fn(mesh, axis_name, cap)(offs_s, ids_s)
        else:
            _, offs_s, ids_s = part.device_slabs()
            out = spgemm_self_slab(offs_s.reshape(-1, offs_s.shape[-1]),
                                   ids_s.reshape(-1, ids_s.shape[-1]),
                                   cap=cap)
        return np.asarray(out).reshape(-1, 2) if to_host \
            else out.reshape(-1, 2)
    _, offs_np, ids_np = part.host_slabs()
    devices = list(mesh.devices.flat) if mesh is not None else None
    bufs = []
    for s in range(part.n_shards):
        if caps[s] == 0:
            continue                    # this shard's buckets are singletons
        offs, ids = offs_np[s], ids_np[s]
        if devices is not None:         # emit on the shard's own device
            offs = jax.device_put(offs, devices[s])
            ids = jax.device_put(ids, devices[s])
        bufs.append(spgemm_self_slab(offs, ids, cap=int(caps[s])))
    # ragged merge: per-shard buffers differ in cap, so the merge is a
    # concat (the cross-shard dedup downstream lexsorts anyway)
    if to_host:
        return np.concatenate(
            [np.asarray(b).reshape(-1, 2) for b in bufs], axis=0)
    if devices is not None:
        # the pack runs as ONE program: gather the per-shard buffers onto
        # the lead device (device-to-device, still no host round-trip)
        bufs = [jax.device_put(b, devices[0]) for b in bufs]
    return jnp.concatenate([b.reshape(-1, 2) for b in bufs], axis=0)


@dataclass(frozen=True)
class JoinPrefilter:
    """Fused in-join ungapped X-drop prefilter (see :func:`lsh_self_join`).

    With this attached, the deduplicated candidate buffer is scored by the
    ungapped diagonal scan ON DEVICE, straight off the device pair buffer,
    and only survivors (ungapped >= ``min_score``) are compacted and copied
    to host — rejected pairs never materialize as host pair arrays. The
    ungapped score is padding-invariant and a lower bound of the SW score,
    so the surviving pair set is bit-exact with filtering
    ``score_pairs(..., prefilter=True)`` output post hoc (same
    ``min_score``/``x``).
    """
    ids: np.ndarray         # (N, L) int8 PAD-padded corpus
    lens: np.ndarray        # (N,) int32
    min_score: int = 40     # survivors: ungapped score >= this (must be >= 1
                            # so the -1 padding slots, which gather all-PAD
                            # rows and score 0, can never survive)
    x: int | None = None    # X-drop margin (None = inf, plain best segment)
    batch: int = 256        # pairs per prefilter chunk (one program shape)
    len_quantum: int = 64   # gathered-length quantization (jit-cache ladder)


@functools.partial(jax.jit, static_argnames=("x", "L", "B"))
@trace_sentinel("join_prefilter")
def _join_prefilter_chunk(ids_dev, lens_dev, pairs_dev, start, *,
                          x: int | None, L: int, B: int):
    """Score one fixed-size chunk of the device pair buffer: fused
    dynamic-slice + gather + ungapped diagonal scan, no host round-trip.
    ``start`` is a traced scalar, so every chunk offset reuses ONE
    compiled program per (x, L, B)."""
    chunk = jax.lax.dynamic_slice(pairs_dev, (start, 0), (B, 2))
    qm = gather_rows(ids_dev, lens_dev, chunk[:, 0], L)
    rm = gather_rows(ids_dev, lens_dev, chunk[:, 1], L)
    return ungapped_xdrop_scores(qm, rm, x=x)


@functools.partial(jax.jit, static_argnames=("cap",))
@trace_sentinel("join_prefilter_pack")
def _prefilter_pack(pairs_dev, scores, min_score, *, cap: int):
    """Compact prefilter survivors (and their ungapped scores) to the
    front of the fixed buffer; (pairs+score (cap, 3) int32, count)."""
    keep = (pairs_dev[:, 0] >= 0) & (scores >= min_score)
    return compact_pairs((pairs_dev[:, 0], pairs_dev[:, 1], scores),
                         keep, cap)


def _prefilter_join(pairs_dev, n_cand: int, pf: JoinPrefilter):
    """Run the fused prefilter over a deduplicated device pair buffer.

    Returns (kept_pairs (K, 2), kept_ungapped (K,) int32) host arrays —
    the only D2H copy of pair data, already survivor-compacted."""
    if pf.min_score < 1:
        raise ValueError("JoinPrefilter.min_score must be >= 1 (padding "
                         "slots score 0 and must never survive)")
    lens_np = np.asarray(pf.lens, np.int32)
    ids_dev = jnp.asarray(pf.ids)
    lens_dev = jnp.asarray(lens_np)
    q = pf.len_quantum
    L = int(max(q, -(-int(lens_np.max(initial=1)) // q) * q))
    cap, B = pairs_dev.shape[0], pf.batch
    # only chunks that can contain real rows are scored; rows past the
    # count are -1 (all-PAD gathers scoring 0) and can never survive
    n_eff = min(cap, -(-max(n_cand, 1) // B) * B)
    pp = (jnp.pad(pairs_dev, ((0, (-cap) % B), (0, 0)), constant_values=-1)
          if cap % B else pairs_dev)
    chunks = [_join_prefilter_chunk(ids_dev, lens_dev, pp,
                                    jnp.asarray(s, jnp.int32),
                                    x=pf.x, L=L, B=B)
              for s in range(0, n_eff, B)]
    scores = jnp.concatenate(chunks)[:cap] if chunks else \
        jnp.zeros(cap, jnp.int32)
    if scores.shape[0] < cap:
        scores = jnp.pad(scores, (0, cap - scores.shape[0]))
    out, cnt = _prefilter_pack(pairs_dev, scores,
                               jnp.asarray(pf.min_score, jnp.int32), cap=cap)
    k = int(cnt)
    host = np.asarray(out[:k])
    return np.ascontiguousarray(host[:, :2]), np.ascontiguousarray(host[:, 2])


@dataclass(frozen=True)
class SelfJoinResult:
    """Deduplicated upper-triangular candidate set as a CSR adjacency."""
    pairs: np.ndarray      # (P, 2) int32, i < j, lexicographically sorted
    indptr: np.ndarray     # (N+1,) int64 — CSR row offsets over corpus ids
    indices: np.ndarray    # (P,) int32 — CSR column ids (the j of each pair)
    n_candidates: int      # == P
    ungapped: np.ndarray | None = None  # (P,) int32 prefilter scores of the
                                        # SURVIVING pairs (fused prefilter)
    n_prefiltered: int = 0  # candidates dropped in-join by the prefilter

    @property
    def n_rows(self) -> int:
        return len(self.indptr) - 1

    def neighbors(self, i: int) -> np.ndarray:
        return self.indices[self.indptr[i]:self.indptr[i + 1]]


def _pairs_to_csr(pairs: np.ndarray, n: int, *, ungapped=None,
                  n_prefiltered: int = 0) -> SelfJoinResult:
    rows = pairs[:, 0]
    indptr = np.searchsorted(rows, np.arange(n + 1)).astype(np.int64)
    return SelfJoinResult(pairs=pairs, indptr=indptr,
                          indices=np.ascontiguousarray(pairs[:, 1]),
                          n_candidates=len(pairs), ungapped=ungapped,
                          n_prefiltered=n_prefiltered)


def _grow_overflow(scope: str, max_grow: int):
    raise RuntimeError(
        f"{scope} exceeded max_grow={max_grow} pairs; the corpus "
        f"has a degenerate bucket (see repro.index.stats) — raise "
        f"max_grow or increase bands/d selectivity")


def _finish_pairs(pairs_dev, n_cand: int, index: SignatureIndex,
                  prefilter: JoinPrefilter | None) -> SelfJoinResult:
    """Shared join tail off a deduplicated DEVICE pair buffer: either the
    fused prefilter (survivors are the only D2H copy) or the plain host
    copy of the first ``n_cand`` rows."""
    if prefilter is None:
        return _pairs_to_csr(np.asarray(pairs_dev[:n_cand]), index.size)
    with span("join_prefilter", cat="allpairs", candidates=n_cand):
        kept, ung = _prefilter_join(pairs_dev, n_cand, prefilter)
    return _pairs_to_csr(kept, index.size, ungapped=ung,
                         n_prefiltered=n_cand - len(kept))


def _dedup_and_pack(cand, index: SignatureIndex,
                    d: int | None, cap: int, max_grow: int, scope: str,
                    prefilter: JoinPrefilter | None = None
                    ) -> SelfJoinResult:
    """Legacy-orchestration tail: cross-band/-shard dedup + optional exact
    Hamming filter under the grow-and-retry capacity discipline (the
    spgemm path sizes the output at the exact emission total instead and
    never retries)."""
    while True:
        pairs, count = spgemm_pack(cand, index.device_sigs,
                                   out_cap=cap, d=d)
        if int(count) <= cap:
            return _finish_pairs(pairs, int(count), index, prefilter)
        if cap >= max_grow:         # dedup union overran the buffer
            _grow_overflow(scope, max_grow)
        cap = min(cap * 2, max_grow)    # grow-and-retry


def _pack_exact(cand_dev, index: SignatureIndex, d: int | None,
                total: int, max_grow: int, scope: str,
                prefilter: JoinPrefilter | None,
                limit: int | None = None) -> SelfJoinResult:
    """SpGEMM-orchestration tail: the pack output is sized at the exact
    emission total (survivors <= emitted always), so it can never
    overflow — no grow-and-retry, one host sync (the count).

    ``limit`` is the legacy-equivalent capacity ceiling
    (``max(starting cap, max_grow)``): legacy only raises when the dedup
    union must GROW past ``max_grow``, so a count the starting buffer
    already covers must succeed here too — never raise where legacy
    would not."""
    limit = max_grow if limit is None else limit
    out_cap = next_pow2(max(1, min(total, limit)))
    pairs, count = spgemm_pack(cand_dev, index.device_sigs,
                               out_cap=out_cap, d=d)
    n_cand = int(count)
    if n_cand > limit:
        _grow_overflow(scope, max_grow)
    return _finish_pairs(pairs, n_cand, index, prefilter)


def _resolve_mesh(n: int, mesh, axis_name: str):
    if n > 1 and mesh is None and jax.device_count() >= n:
        mesh = _default_mesh(n, axis_name)
    if mesh is not None and (axis_name not in mesh.axis_names
                             or mesh.shape[axis_name] != n):
        # shard_fn emits block[0] only — a smaller mesh would silently
        # drop the other shards' pairs
        raise ValueError(
            f"mesh axes {dict(mesh.shape)} do not provide {n} devices on "
            f"axis {axis_name!r} (one per partition shard)")
    if n == 1:
        mesh = None     # a 1-ring shard_map would only add dispatch cost
    return mesh


def lsh_self_join(index: SignatureIndex, *, d: int | None = None,
                  max_pairs: int = 1 << 16,
                  max_grow: int = 1 << 24,
                  n_shards: int | None = None,
                  mesh=None, axis_name: str = "data",
                  prefilter: JoinPrefilter | None = None,
                  join_impl: str = "spgemm") -> SelfJoinResult:
    """All-pairs candidate generation over the indexed corpus.

    Emits every within-bucket pair of every band, deduplicates across bands
    (and shards), and (optionally, ``d=``) exact-filters by packed Hamming
    distance. ``n_shards`` (default: the index's own ``n_shards``) routes
    emission through the bucket partition: with a mesh — ``mesh=`` or, when
    the process has that many devices, the first ``n_shards`` of
    ``jax.devices()`` — each shard emits its buckets' pairs on its own
    device in parallel; the pair set (and the result arrays) are
    bit-identical for every ``n_shards``.

    ``join_impl="spgemm"`` (default) fuses emission + dedup + filter +
    compaction device-resident and sizes the output at the exact emission
    total (no grow-and-retry, one host sync); ``"legacy"`` is the
    pre-SpGEMM orchestration (host merge + grow-and-retry), kept one PR as
    the bit-exactness reference — both produce identical arrays.

    Capacity discipline: per-shard emission capacity is sized from host-side
    int64 bucket totals (the device-side int32 count would wrap for a
    degenerate ~66k-member bucket and truncate silently), each shard at its
    OWN demand (:func:`_shard_caps` — skew-bounded); demand beyond
    ``max_grow`` raises — never a silent cap.

    ``prefilter=`` fuses the ungapped X-drop prefilter into the join
    (:class:`JoinPrefilter`): candidates are scored off the deduplicated
    DEVICE pair buffer and rejected pairs never reach the host — the
    returned pairs are exactly the survivors (``result.ungapped`` holds
    their prefilter scores, ``result.n_prefiltered`` the rejected count).
    """
    _check_impl(join_impl)
    n = int(n_shards) if n_shards is not None else index.n_shards
    part = index.partition(n)
    # the overflow check judges TRUE demand (the quantized caps below only
    # size buffers — quantization must never turn a legal corpus into an
    # error for non-pow2 max_grow values)
    need = int(part.pair_totals.max()) if part.pair_totals.size else 0
    if need > max_grow:
        _grow_overflow("self-join", max_grow)
    if need == 0:       # every bucket is a singleton: no collisions at all
        return _pairs_to_csr(np.zeros((0, 2), np.int32), index.size)
    caps = _shard_caps(part)
    mesh = _resolve_mesh(n, mesh, axis_name)
    # Emission runs ONCE at per-shard exact-or-2x capacity (it can never
    # truncate); only the deduplicated cross-shard union can grow (legacy)
    # — the spgemm pack is sized at the exact emission total instead.
    with span("emission", cat="allpairs", shards=n, impl=join_impl,
              spmd=mesh is not None, need=need):
        if join_impl == "legacy":
            cand = _emit_partition(part, caps, mesh, axis_name)
            cap = max(max_pairs, int(caps.max()))
            return _dedup_and_pack(cand, index, d, cap, max_grow,
                                   "self-join", prefilter=prefilter)
        total = int(part.pair_totals.sum())
        # legacy-equivalent ceiling: legacy starts at max(max_pairs, caps)
        # and only raises when the union must GROW past max_grow
        limit = max(max_pairs, int(caps.max()), max_grow)
        live = caps[caps > 0]
        uniform = live.size == 0 or int(live.min()) == int(live.max())
        if mesh is None and uniform:
            # the fully fused program: products + dedup + filter + compact
            _, offs_s, ids_s = part.device_slabs()
            offs_f = offs_s.reshape(-1, offs_s.shape[-1])
            ids_f = ids_s.reshape(-1, ids_s.shape[-1])
            out_cap = next_pow2(max(1, min(total, limit)))
            if index.layout == "band" and index.size <= PACKED_KEY_MAX_ID:
                # band layout: duplicates only arise ACROSS bands and the
                # band-key matrix detects them at emission, so the pack is
                # one sort of packed keys — no dedup pass at all
                band_f = jnp.tile(
                    jnp.arange(offs_s.shape[1], dtype=jnp.int32),
                    offs_s.shape[0])
                pairs, count = spgemm_join_self_keys(
                    offs_f, ids_f, band_f, index.device_band_keys,
                    index.device_sigs, cap=int(caps.max()),
                    out_cap=out_cap, d=d)
            else:
                pairs, count = spgemm_join_self(
                    offs_f, ids_f, index.device_sigs,
                    cap=int(caps.max()), out_cap=out_cap, d=d)
            n_cand = int(count)
            if n_cand > limit:
                _grow_overflow("self-join", max_grow)
            return _finish_pairs(pairs, n_cand, index, prefilter)
        # SPMD or skewed demand: per-shard products, device-side merge,
        # fused pack — still no host round-trip of candidate rows
        cand = _emit_partition(part, caps, mesh, axis_name, to_host=False)
        return _pack_exact(cand, index, d, total, max_grow, "self-join",
                           prefilter, limit=limit)


def _segment_stack(seg, n_shards: int = 1):
    """One sealed segment's delta-join arrays for one shard count, CACHED
    ON THE SEGMENT (sealed = immutable, so they are built once per segment
    lifetime, not once per ingest — resident segments stay cheap across
    ``--incremental`` rounds): the :class:`BucketPartition` (band-stacked
    per-shard slabs + exact per-(shard, band) pair totals, the single
    stacking code path) and its pow2-quantized host slabs
    (:func:`~repro.index.partition.pad_slabs_pow2` — shapes repeat across
    ingests, keeping the jitted emission programs cache-hot)."""
    cache = getattr(seg, "_join_stacks", None)
    if cache is None:
        cache = {}
        seg._join_stacks = cache
    cached = cache.get(n_shards)
    if cached is None:
        part = BucketPartition(seg.csr, n_shards)
        keys_s, offs_s, ids_s = (np.asarray(a) for a in part.host_slabs())
        slabs = pad_slabs_pow2(keys_s, offs_s, ids_s)   # (S, nb, ...) stacks
        cached = (part, slabs)
        cache[n_shards] = cached
    return cached


def _cross_totals(dpart: BucketPartition, rpart: BucketPartition
                  ) -> np.ndarray:
    """Exact int64 cross-pair totals per (shard, band) between a delta
    partition's buckets and a resident partition's matching buckets
    (host-side — the capacity sizing must never wrap). Bucket ownership is
    keyed on the bucket key, so matching buckets always land on the SAME
    shard of both partitions — the per-shard cross products cover exactly
    the unsharded cross product."""
    out = np.zeros((dpart.n_shards, dpart.n_bands), np.int64)
    for s in range(dpart.n_shards):
        for b in range(dpart.n_bands):
            dk, do, _ = dpart.shards[s][b]
            rk, ro, _ = rpart.shards[s][b]
            if len(dk) == 0 or len(rk) == 0:
                continue
            dn = np.diff(do).astype(np.int64)
            pos = np.searchsorted(rk, dk)
            pos_c = np.clip(pos, 0, len(rk) - 1)
            match = (pos < len(rk)) & (rk[pos_c] == dk)
            rn = np.where(match,
                          (np.asarray(ro)[pos_c + 1] - np.asarray(ro)[pos_c]
                           ).astype(np.int64), 0)
            out[s, b] = int((dn * rn).sum())
    return out


def _flat(a):
    """(S, nb, X) slab -> (S*nb, X) for the band-stacked product programs."""
    return a.reshape(-1, a.shape[-1])


def lsh_delta_join(index: SignatureIndex, *, base_size: int,
                   d: int | None = None,
                   max_pairs: int = 1 << 16,
                   max_grow: int = 1 << 24,
                   n_shards: int | None = None,
                   mesh=None, axis_name: str = "data",
                   prefilter: JoinPrefilter | None = None,
                   join_impl: str = "spgemm") -> SelfJoinResult:
    """Incremental self-join: only the pairs touching rows >= ``base_size``.

    ``base_size`` must be a segment boundary (the corpus size before the
    ``add()`` calls being ingested). For each new segment the join emits
    its within-bucket pairs (upper mask over the delta slab) plus its
    cross pairs against the matching buckets of every earlier segment
    (the ``Aᵀ_delta · A_resident`` cross mask) — resident-vs-resident
    pairs are never re-enumerated, so ingest cost scales with the delta's
    bucket footprint, not the corpus. With ``n_shards > 1`` (default: the
    index's own) both masks run per shard under the segment bucket
    partitions — matching keys own the same shard on both sides, so the
    per-shard union is exactly the unsharded pair set; with a mesh each
    shard emits on its own device (``shard_map``). The result unions with
    the pre-ingest pair set to EXACTLY the from-scratch
    :func:`lsh_self_join` over the grown corpus (same dedup, same optional
    Hamming filter, same sort order); tests/test_lifecycle.py asserts the
    equality. ``join_impl="legacy"`` keeps the pre-SpGEMM single-device
    orchestration for one PR (identical arrays).
    """
    _check_impl(join_impl)
    index.seal()
    segs = index.segments
    boundaries = [s.base for s in segs] + [index.size]
    if base_size not in boundaries:
        raise ValueError(
            f"base_size {base_size} is not a segment boundary "
            f"{boundaries}; delta joins ingest whole segments")
    if base_size == index.size:     # nothing new
        return _pairs_to_csr(np.zeros((0, 2), np.int32), index.size)
    k = boundaries.index(base_size)
    n = 1 if join_impl == "legacy" else (
        int(n_shards) if n_shards is not None else index.n_shards)
    mesh = _resolve_mesh(n, mesh, axis_name)

    def part(i) -> BucketPartition:
        return _segment_stack(segs[i], n)[0]

    def slabs(i):
        # pow2-quantized shapes + pow2 caps keep the jitted emission
        # programs cache-hot across successive ingests (exact shapes/caps
        # would retrace per segment — the recompile trap this PR fixes
        # everywhere else)
        return _segment_stack(segs[i], n)[1]

    def emit_within(i, cap: int):
        keys_s, offs_s, ids_s = slabs(i)
        if mesh is not None:
            sharding = NamedSharding(mesh, P(axis_name))
            return _emit_sharded_fn(mesh, axis_name, cap)(
                jax.device_put(offs_s, sharding),
                jax.device_put(ids_s, sharding))
        return spgemm_self_slab(_flat(offs_s), _flat(ids_s), cap=cap)

    def emit_cross(s, r, cap: int):
        dk, do, di = slabs(s)
        rk, ro, ri = slabs(r)
        if mesh is not None:
            sh = NamedSharding(mesh, P(axis_name))
            args = [jax.device_put(a, sh) for a in (dk, do, di, rk, ro, ri)]
            return _emit_cross_sharded_cached(
                tuple(mesh.devices.flat), axis_name, cap)(*args)
        return spgemm_cross_slab(_flat(dk), _flat(do), _flat(di),
                                 _flat(rk), _flat(ro), _flat(ri), cap=cap)

    bufs = []
    total = 0
    with span("delta_emission", cat="allpairs", shards=n, impl=join_impl,
              new_segments=len(segs) - k, resident_segments=k):
        for s in range(k, len(segs)):
            within = part(s).pair_totals
            need_w = int(within.max(initial=0))
            if need_w > max_grow:
                _grow_overflow("delta join", max_grow)
            if need_w > 0:
                total += int(within.sum())
                bufs.append(emit_within(s, next_pow2(need_w)))
            for r in range(s):      # every earlier segment is resident
                totals = _cross_totals(part(s), part(r))
                need_c = int(totals.max(initial=0))
                if need_c > max_grow:
                    _grow_overflow("delta join", max_grow)
                if need_c == 0:
                    continue
                total += int(totals.sum())
                bufs.append(emit_cross(s, r, next_pow2(need_c)))
        if not bufs:
            return _pairs_to_csr(np.zeros((0, 2), np.int32), index.size)
        if join_impl == "legacy":
            # ragged host merge (buffers differ in cap); dedup lexsorts
            cand = np.concatenate(
                [np.asarray(b).reshape(-1, 2) for b in bufs], axis=0)
            return _dedup_and_pack(cand, index, d, max_pairs, max_grow,
                                   "delta join", prefilter=prefilter)
        # spgemm: device-side ragged merge + exact-sized fused pack
        cand = jnp.concatenate([b.reshape(-1, 2) for b in bufs], axis=0)
    return _pack_exact(cand, index, d, total, max_grow, "delta join",
                       prefilter, limit=max(max_pairs, max_grow))


def brute_force_collisions(index: SignatureIndex) -> set[tuple[int, int]]:
    """Oracle: enumerate all within-bucket pairs with host loops (exactness
    reference for tests/benchmarks — O(sum m^2), small corpora only)."""
    index._ensure_built()
    out: set[tuple[int, int]] = set()
    for (keys, offsets, ids) in index._csr_np:
        ids = np.asarray(ids)
        offsets = np.asarray(offsets)
        for u in range(len(keys)):
            members = ids[offsets[u]:offsets[u + 1]]
            for a in range(len(members)):
                for b in range(a + 1, len(members)):
                    i, j = int(members[a]), int(members[b])
                    out.add((min(i, j), max(i, j)))
    return out
