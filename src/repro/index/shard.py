"""Bucket-sharded probe serving: shards own buckets, query blocks rotate.

The MapReduce analogue made literal: each shard of the mesh owns the
buckets that :func:`repro.index.partition.bucket_owners` routes to it
(``mix32(band_key) % n_shards`` — the shuffle), holding them as a
self-contained stacked-padded CSR slab *including its bucket entries'
signature rows* — no shard ever holds the full (N, nw) signature matrix,
so index memory scales down with the mesh. Serving probes run
shard-local: the query batch is split into per-shard blocks that rotate
around the mesh with ``ppermute`` (the ``ring_sweep`` discipline from
:mod:`repro.core.mapreduce`) in a **two-phase** sweep:

* **phase 1 — collect**: each hop only *searchsorts* the resident slab
  (core shared with the single-device probe, ``_probe_csr_positions``)
  and writes the candidate ids + their signature rows into the block's
  carried candidate buffers. A (band, key) bucket is owned by exactly
  one shard, so each buffer slot is written on exactly one hop — the
  non-owning hops touch nothing.
* **phase 2 — score at home**: after ``n_shards`` hops the buffers are
  back at the block's home shard, which runs ONE Hamming-distance pass
  over the collected ``nb*cap`` candidates, then the shared dedup +
  top-k tail. The old ring scored all ``nb*cap`` visiting slots on
  *every* hop even though non-owners match nothing — ``n_shards``-fold
  more distance work (and a per-hop top-k merge) for the same result.

No dense sweep, no global-id arithmetic (buckets store global ids
directly); per-hop communication is the rotating query keys + the
candidate id/signature buffers.

Growth is a **delta refresh**, not a re-place: references appended with
``index.add()`` arrive as sealed segments, and because
``mix32(key) % n_shards`` never changes a bucket's owner, :meth:`refresh`
partitions just the new segments and uploads them as a second, small
*delta slab* per shard. Each ring hop probes base + delta and sums the
matched-bucket sizes, so the grow-and-retry overflow contract sees the
same true bucket sizes as a merged table — results are **bit-exact with a
compacted rebuild** (asserted in tests/test_lifecycle.py). When the delta
outgrows the base (or after ``index.compact()``), :meth:`compact`
re-places everything into one base slab; probe results are identical
before and after.

Exactness: buckets are never split across shards, so the union of
per-shard collections is exactly the single-device candidate set, the
collected signature rows are exactly ``ref_sigs[cand]``, and the home
pass is literally ``topk_probe``'s filter — one Hamming sweep, the shared
``_dedup_candidates`` (distance, id) tie-break, one top-k — so results
are bit-exact with :func:`repro.index.service.topk_probe` for every
``n_shards`` — including tie-breaks — and overflow detection (true
matched-bucket size vs cap) is the max over all (shard, hop) probes, the
same grow-and-retry contract.
Both layouts partition identically — the flip layout's single expanded
table is just ``n_bands == 1`` (tested under sharding in
tests/test_sharding.py).
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.hamming import hamming_distance
from ..obs import span, trace_sentinel
from ..obs.trace import record as record_span
from .partition import pad_slabs_pow2
from .service import BIG, _dedup_candidates, _probe_csr_positions
from .store import SignatureIndex


def _merge_topk(best_id, best_d, cand, dist, k: int):
    """Fold new candidates into a carried top-k under the total order
    (distance, id): concat, dedup by id (``_dedup_candidates`` — a
    candidate re-surfacing on a later hop has the same exact distance),
    keep the best k. The shared sort-by-id dedup breaks distance ties
    toward the smaller id, exactly like ``_topk_from_candidates``.

    best_id/best_d (B, K) carried accumulator (-1 / BIG in empty slots);
    cand/dist (B, C) this hop's candidates (dist == BIG where masked).
    """
    ids_all = jnp.concatenate([best_id, cand], axis=1)
    d_all = jnp.concatenate([best_d, dist], axis=1)
    ii, dvals = _dedup_candidates(ids_all, d_all, d_all < BIG)
    neg, idx = jax.lax.top_k(-dvals, k)
    nd = -neg
    nid = jnp.take_along_axis(ii, idx, axis=1)
    nid = jnp.where(nd < BIG, nid, -1)
    nd = jnp.where(nd < BIG, nd, BIG)
    return nid, nd


@functools.lru_cache(maxsize=128)
def _ring_program(devices: tuple, axis_name: str, Bl: int, cap: int, k: int,
                  has_delta: bool):
    """The jitted two-phase shard_map ring program, cached at MODULE level
    by the device tuple (never a Mesh object or a replica instance) — the
    same keying lesson as the self-join's emission cache: equal meshes and
    every replica over them share one compiled program, so constructing a
    new ShardedIndex (or refreshing one) never silently recompiles a ring
    it has already paid for. The ``has_delta`` variant collects from the
    base and delta slabs each hop and sums their matched-bucket sizes (the
    merged-table overflow contract)."""
    ax = axis_name
    mesh = Mesh(np.array(devices), (ax,))
    n = len(devices)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def collect_slab(qk_c, keys_l, offs_l, ids_l, esig_l):
        """Phase-1 collection on one slab: candidate ids + their signature
        rows for the slots this shard owns -> cand (nb, Bl, cap) int32
        (-1 where unmatched), sig (nb, Bl, cap, nw), size (nb, Bl). No
        distance work — that happens once, at home."""
        E = ids_l.shape[1]

        def collect_band(qk_b, keys_b, offs_b, ids_b, esig_b):
            idx, ok, size = _probe_csr_positions(qk_b, keys_b, offs_b,
                                                 cap=cap, E=E)
            cand = jnp.where(ok, ids_b[idx], -1)
            sig = jnp.where(ok[..., None], esig_b[idx], 0)
            return cand, sig, size

        return jax.vmap(collect_band, in_axes=(1, 0, 0, 0, 0))(
            qk_c, keys_l, offs_l, ids_l, esig_l)

    @trace_sentinel("ring", static_key=(devices, Bl, cap, k, has_delta))
    def shard_fn(qk, qs, *slabs):
        # qk (Bl, nb), qs (Bl, nw) — this shard's starting query block;
        # slabs arrive (1, nb, ...) after the P(ax) split: base
        # (keys, offs, ids, esig) then, when present, the delta four.
        # qs never rotates: the one distance pass runs at home (phase 2).
        base = tuple(a[0] for a in slabs[:4])
        delta = tuple(a[0] for a in slabs[4:8]) if has_delta else None
        nw = base[3].shape[-1]
        C = qk.shape[1] * cap * (2 if has_delta else 1)

        def hop(carry, _):
            qk_c, idb, sgb, msz = carry
            cand, sig, size = collect_slab(qk_c, *base)
            if delta is not None:
                c2, s2, z2 = collect_slab(qk_c, *delta)
                # a bucket split across base+delta is ONE bucket of the
                # merged table: candidates union, true size is the sum
                cand = jnp.concatenate([cand, c2], axis=2)
                sig = jnp.concatenate([sig, s2], axis=2)
                size = size + z2
            # (nb, Bl, cap) -> (Bl, nb*cap), the fused-probe layout
            cand = jnp.transpose(cand, (1, 0, 2)).reshape(Bl, -1)
            sig = jnp.transpose(sig, (1, 0, 2, 3)).reshape(Bl, -1, nw)
            ok = cand >= 0
            # each (query, band) bucket is owned by exactly one shard, so
            # each slot is written on exactly one hop — where() is a union
            idb = jnp.where(ok, cand, idb)
            sgb = jnp.where(ok[..., None], sig, sgb)
            msz = jnp.maximum(msz, jnp.max(size))
            # rotate the block's keys and candidate buffers one hop
            # (ring_sweep discipline); after n hops they are home
            qk_c = jax.lax.ppermute(qk_c, ax, perm)
            idb = jax.lax.ppermute(idb, ax, perm)
            sgb = jax.lax.ppermute(sgb, ax, perm)
            return (qk_c, idb, sgb, msz), None

        init = (qk,
                jnp.full((Bl, C), -1, jnp.int32),
                jnp.zeros((Bl, C, nw), jnp.uint32),
                jnp.zeros((), jnp.int32))
        (_, idb, sgb, msz), _ = jax.lax.scan(hop, init, None, length=n)
        # phase 2: ONE Hamming pass over the collected candidates at home,
        # then the shared dedup + top-k tail — exactly topk_probe's filter
        dist = hamming_distance(qs[:, None, :], sgb)
        dist = jnp.where(idb >= 0, dist, BIG)
        bid, bd = _merge_topk(jnp.full((Bl, k), -1, jnp.int32),
                              jnp.full((Bl, k), BIG, jnp.int32),
                              idb, dist, k)
        return bid, bd, msz[None]

    n_args = 10 if has_delta else 6
    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(P(ax) for _ in range(n_args)),
        out_specs=(P(ax), P(ax), P(ax)), check_vma=False,
    ))


class ShardedIndex:
    """A :class:`SignatureIndex` whose *buckets* are laid out over a mesh."""

    def __init__(self, index: SignatureIndex, mesh=None,
                 *, axis_name: str = "data"):
        self.index = index
        self.axis_name = axis_name
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (axis_name,))
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh has axes {mesh.axis_names}, expected "
                             f"{axis_name!r}")
        # Auto axes, as the ring's own mesh has: a caller's Explicit mesh
        # (``jax.make_mesh``'s default) would put its axis into the slabs'
        # types, and jit would trace the same ring program once per kind
        # of mesh.
        self.mesh = Mesh(mesh.devices, mesh.axis_names)
        self.n_shards = mesh.shape[axis_name]
        # Serializes this replica's slab swaps AND the backing index's
        # lazy lifecycle mutations (seal/merge/partition) that refresh
        # triggers. Reentrant because refresh() takes it and is also
        # called under it from _refresh_if_stale. A replica fleet
        # (repro.serve.fleet) swaps in ONE lock shared by every replica
        # and the ingest thread, so a concurrent ``index.add()`` can
        # never interleave with a replica sealing/partitioning the same
        # segments (torn reads). Single-threaded use pays one uncontended
        # RLock acquire per staleness check.
        self.refresh_lock = threading.RLock()
        self._place()

    # ------------------------------------------------------------ placement
    def _put(self, part, quantize: bool = True):
        """Slabs go straight from host to their owning devices with a
        ``NamedSharding`` split on the shard axis — no single device ever
        materializes the full stack, and the jitted ring (whose in_specs
        expect exactly this layout) never reshards on the serving path.

        ``quantize`` pads the bucket (U) and entry (E) axes to powers of
        two (:func:`repro.index.partition.pad_slabs_pow2` — the shared
        inert-padding discipline) so repeated placements repeat slab
        shapes and the ring program stays jit-cache-hot. Originally only
        the DELTA slabs were quantized; the recompile sentinel
        (repro.obs.jit) showed the BASE slabs retracing the ring on every
        compaction (+32 refs = new exact E = new program), so the base is
        now quantized too — a major compaction only recompiles when a
        slab genuinely crosses a power-of-two bin."""
        keys, offs, ids = part.host_slabs()
        esig = part.host_entry_sigs()
        if quantize and ids.shape[-1] > 0:
            keys, offs, ids, esig = pad_slabs_pow2(keys, offs, ids, esig)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        slabs = tuple(jax.device_put(a, sharding)
                      for a in (keys, offs, ids))
        esigs = jax.device_put(esig, sharding)
        return slabs, esigs

    def _place(self) -> None:
        """Full (re)placement: every segment merged into the base slabs.
        Paid at construction, after ``index.compact()``, and when the
        delta outgrows the base — never on a routine refresh."""
        index = self.index
        index.seal()
        with span("place", cat="lifecycle", shards=self.n_shards,
                  epoch=index.epoch):
            part = index.partition(self.n_shards)
            self._slabs, self._esigs = self._put(part)
        self._part = part
        self._delta = None          # (slabs, esigs) of segments past base
        self._delta_part = None
        self._gen = index.generation
        self._base_epoch = index.epoch
        self._delta_epoch = index.epoch

    def refresh(self) -> None:
        """Ingest segment deltas without a full reload.

        Bucket owners never change (``mix32(key) % n_shards`` is id-free),
        so segments sealed since the base placement partition on their own
        and ride along as per-shard delta slabs; upload cost is O(delta).
        Falls back to a full re-place when the index was compacted
        (generation bump), the base is empty, or the delta has outgrown
        the base (at which point merging is cheaper than carrying both).
        """
        with self.refresh_lock:
            index = self.index
            index.seal()
            if index.generation != self._gen:
                self._place()       # compaction collapsed our base segments
                return
            if index.epoch == self._delta_epoch:
                return              # nothing new
            base_keys = self._slabs[0]
            if base_keys.shape[2] == 0:     # empty base: just re-place
                self._place()
                return
            dpart = self.index.delta_partition(self.n_shards,
                                               self._base_epoch)
            if int(dpart.n_entries.sum()) >= int(self._part.n_entries.sum()):
                self._place()       # delta outgrew base: compact placement
                return
            if int(dpart.n_buckets.sum()) == 0:  # only invalid rows arrived
                self._delta_epoch = index.epoch
                return
            with span("refresh", cat="lifecycle",
                      from_epoch=self._delta_epoch, to_epoch=index.epoch,
                      entries=int(dpart.n_entries.sum())):
                self._delta = None  # drop the old delta before realloc
                delta_slabs, delta_esigs = self._put(dpart)
                self._delta = (delta_slabs, delta_esigs)
            self._delta_part = dpart
            self._delta_epoch = index.epoch

    def compact(self) -> None:
        """Fold the delta slabs back into one base placement (serving-side
        compaction; probe results are identical before and after)."""
        with self.refresh_lock:
            with span("compact_serving", cat="lifecycle",
                      epoch=self.index.epoch):
                self._place()

    def _refresh_if_stale(self) -> None:
        with self.refresh_lock:
            if (self.index.generation, self.index.epoch) != \
                    (self._gen, self._delta_epoch):
                self.refresh()

    @property
    def size(self) -> int:
        return self.index.size

    @property
    def epoch(self) -> tuple[int, int]:
        """(base_epoch, delta_epoch) segment counters this replica serves."""
        return (self._base_epoch, self._delta_epoch)

    # ------------------------------------------------------------ ring
    def _ring_fn(self, Bl: int, cap: int, k: int, has_delta: bool):
        """Resolve this replica's mesh to the module-cached ring program
        (serving hot path, no per-call or per-replica re-trace)."""
        return _ring_program(tuple(self.mesh.devices.flat), self.axis_name,
                             Bl, cap, k, has_delta)

    def topk(self, q_sigs, *, k: int, cap: int = 32, max_cap: int = 1 << 14):
        """Global top-k via shard-local bucket probes.

        (B, nw) query signatures -> (ids (B, k), dists (B, k), final_cap,
        truncated), both -1-padded — bit-exact with
        :func:`~repro.index.service.topk_probe` (same candidates, same
        tie-breaks, same grow-and-retry overflow contract), whether the
        placement is one base slab or base + delta (live refresh).
        """
        self._refresh_if_stale()
        q = np.asarray(q_sigs, np.uint32)
        B = q.shape[0]
        n = self.n_shards
        n_buckets = self._slabs[0].shape[2]
        if self._delta is not None:
            n_buckets += self._delta[0][0].shape[2]
        if B == 0 or n_buckets == 0:    # no queries / no buckets at all
            return (np.full((B, k), -1, np.int32),
                    np.full((B, k), -1, np.int32), cap, False)
        qk = np.asarray(self.index.query_keys(q)).T     # (B, nb)
        Bl = max(-(-B // n), 1)
        # padding rows replicate query 0: real keys, so they can only
        # re-match buckets query 0 already probed — the overflow max and
        # the (cap, truncated) contract stay bit-exact with topk_probe
        # (all-zero padding keys could match a real key-0 bucket that no
        # actual query probes)
        qk_p = np.tile(qk[:1], (Bl * n, 1))
        qk_p[:B] = qk
        qs_p = np.tile(q[:1], (Bl * n, 1))
        qs_p[:B] = q
        t_ring = time.perf_counter()
        while True:
            fn = self._ring_fn(Bl, cap, k, self._delta is not None)
            args = (qk_p, qs_p, *self._slabs, self._esigs)
            if self._delta is not None:
                args = args + (*self._delta[0], self._delta[1])
            bid, bd, msz = fn(*args)
            truncated = int(np.max(np.asarray(msz))) > cap
            if not truncated or cap >= max_cap:
                break
            cap = min(cap * 2, max_cap)     # grow-and-retry
        record_span("ring_probe", t_ring, time.perf_counter(), B=B,
                    shards=n, cap=cap, truncated=truncated,
                    delta=self._delta is not None)
        nid = np.array(bid[:B])
        nd = np.array(bd[:B])
        nd[nd >= BIG] = -1
        return nid, nd, cap, truncated
