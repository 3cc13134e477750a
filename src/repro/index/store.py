"""Signature index over a reference database — built once, grown forever.

Structure (DESIGN.md §2 "HDFS -> on-device buffers + manifests"):

* packed signatures ``sigs`` (N, f//32) uint32 — job 1's output, persisted;
* ``valid`` (N,) bool — the paper's non-zero-signature rule (§5.2): sequences
  with zero neighbour features collapse to the all-ones fingerprint and are
  excluded from every bucket;
* per-band sorted buckets in CSR form: for each band, the sorted unique
  bucket ``keys`` (U,), ``offsets`` (U+1,) into ``ids`` (E,) — the reference
  ids grouped by bucket. Two layouts:

  - ``layout="band"`` (default): keys from :func:`repro.core.join.band_keys`
    with ``bands >= d+1`` — the pigeonhole guarantee of ``band_join`` (any
    pair within Hamming d agrees exactly on >= 1 band), so a probe of all
    bands has no false negatives within d.
  - ``layout="flip"``: the paper-faithful expansion — every reference emits
    all C(f, <=d) bit-flips (:func:`repro.core.join.flip_masks`) as keys and
    queries probe with their raw signature; one sorted array, exact, no
    duplicate candidates. f <= 32.

Growth is **append-only** (:mod:`repro.index.segments`): every ``add()``
seals a new segment (its own CSR buckets over global ids) and resident
segments are never re-bucketed. The merged bucket table consumers probe
against is a stable linear merge of the segment tables — bit-exact with a
from-scratch build — materialized lazily and only for consumers that need
the whole table (the single-device probe, a full partition, a legacy
save); the serving ring ingests segment *deltas* instead
(:meth:`repro.index.shard.ShardedIndex.refresh`). ``compact()`` is the
explicit reduce step: it folds every segment into one.

The stacked-padded slabs every probe/join consumer runs against are built
by the bucket partition layer (:mod:`repro.index.partition`) via
:meth:`SignatureIndex.partition` — the single-device probe is just shard 0
of the 1-way partition.

Persistence is fingerprint-versioned (the LSH parameters that determine
signature semantics; ``n_shards`` joins it when != 1, and pre-sharding
fingerprints stay valid) in two containers: a **segment directory**
(manifest + per-segment npz, appends cost O(delta)) or the legacy
monolithic ``.npz`` (paths ending in ``.npz``; what PR 1–4 wrote, still
read and written for compatibility). Loading an index against a different
:class:`~repro.core.pipeline.LSHConfig` raises :class:`IndexConfigMismatch`
— a stale index never silently serves wrong candidates.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import zipfile

import jax.numpy as jnp
import numpy as np

from ..core.pipeline import LSHConfig, ScalLoPS
from ..core.join import band_keys
from ..faults import atomic_write
from ..obs import span
from . import segments as seglib
from .segments import CorruptSegment, Segment

FORMAT_VERSION = 1

# Fields of LSHConfig that determine signature/bucket semantics. Serving-time
# knobs (max_pairs, join_method) are deliberately excluded: changing them must
# not invalidate a persisted index.
_FINGERPRINT_FIELDS = ("k", "T", "f", "d", "scheme", "siggen_method")


class IndexConfigMismatch(RuntimeError):
    """A persisted index was loaded against an incompatible LSHConfig."""


def config_fingerprint(cfg: LSHConfig, *, layout: str, bands: int,
                       interleave: bool = True,
                       key_hash: str = "none",
                       n_shards: int = 1) -> str:
    """Stable 16-hex-digit fingerprint of the index-relevant config."""
    payload = {
        "cfg": {f: getattr(cfg, f) for f in _FINGERPRINT_FIELDS},
        "layout": layout, "bands": bands, "interleave": interleave,
        "format": FORMAT_VERSION,
    }
    # key_hash="none" is omitted so pre-key-hash fingerprints stay valid
    if key_hash != "none":
        payload["key_hash"] = key_hash
    # n_shards=1 is omitted so pre-sharding fingerprints stay valid
    if n_shards != 1:
        payload["n_shards"] = n_shards
    blob = json.dumps(payload, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class SignatureIndex:
    """Segmented reference index over packed LSH signatures.

    Use :meth:`build` (from sequences) or :meth:`load` (from disk); query
    via :meth:`probe` / the serving layer (:mod:`repro.index.service`);
    grow via :meth:`add` (seals an append-only segment).
    """

    def __init__(self, cfg: LSHConfig, sigs: np.ndarray, valid: np.ndarray,
                 *, layout: str = "band", bands: int | None = None,
                 interleave: bool = True, key_hash: str = "splitmix",
                 n_shards: int = 1):
        if layout not in ("band", "flip"):
            raise ValueError(f"unknown index layout {layout!r}")
        if layout == "flip" and cfg.f > 32:
            raise ValueError("flip layout needs f <= 32 (paper used f=32)")
        if key_hash not in ("splitmix", "none"):
            raise ValueError(f"unknown key_hash {key_hash!r}")
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.cfg = cfg
        self.layout = layout
        # Intended bucket-shard count (the MapReduce reducer count). Purely
        # a placement property — bucket contents are identical for every
        # n_shards — but persisted (and fingerprinted when != 1) so a
        # serving replica reloads the same partition it was built for.
        self.n_shards = int(n_shards)
        # Interleaved banding (bit i -> band i % bands) spreads the
        # position-skewed signature-bit entropy evenly; see band_bit_groups.
        self.interleave = bool(interleave)
        # Serving default: splitmix-mix band keys before bucketing (a
        # bijection — bucket membership is untouched; key *arithmetic*
        # becomes skew-free, the ROADMAP "hash band keys" follow-on).
        # key_hash="none" keeps the raw band bits for paper-fidelity runs.
        self.key_hash = key_hash if layout == "band" else "none"
        self.bands = int(bands if bands is not None else max(cfg.d + 1, 1))
        if layout == "band" and self.bands < cfg.d + 1:
            raise ValueError("bands must be >= d+1 for an exact probe")
        self.sigs = np.ascontiguousarray(np.asarray(sigs, np.uint32))
        self.valid = np.asarray(valid, bool).reshape(-1).copy()
        assert self.sigs.shape == (self.valid.shape[0], cfg.f // 32)
        # -------- append-only lifecycle state
        self.segments: list[Segment] = []   # sealed (CSR built)
        self._pending: list[tuple] = []     # (sigs, valid, base) to seal
        if self.size:
            self._pending.append((self.sigs, self.valid, 0))
        self.generation = 0         # bumps on compact() (forest of segments
                                    # collapsed — delta consumers re-place)
        self._merged_stale = True   # merged CSR needs a (re)merge
        self._csr_np = None         # merged per-band CSR (lazy)
        self.recovery = None        # set by load(recover=True) when a
                                    # damaged tail was quarantined
        self._partitions = {}       # n_shards -> BucketPartition (slabs)
        self._dev_sigs = None
        self._dev_valid = None
        self._dev_band_keys = None
        self._pipeline = None

    # ------------------------------------------------------------ properties
    @property
    def size(self) -> int:
        return self.sigs.shape[0]

    @property
    def n_bands(self) -> int:
        return 1 if self.layout == "flip" else self.bands

    @property
    def epoch(self) -> int:
        """Segment count (sealed + pending) — the serving layers' staleness
        counter: a replica that last saw epoch e ingests segments[e:]."""
        return len(self.segments) + len(self._pending)

    @property
    def lifecycle(self) -> tuple[int, int]:
        """(generation, epoch) — changes iff a delta refresh or a full
        re-place is due."""
        return (self.generation, self.epoch)

    @property
    def fingerprint(self) -> str:
        return config_fingerprint(self.cfg, layout=self.layout,
                                   bands=self.bands,
                                   interleave=self.interleave,
                                   key_hash=self.key_hash,
                                   n_shards=self.n_shards)

    @property
    def device_sigs(self) -> jnp.ndarray:
        if self._dev_sigs is None or self._dev_sigs.shape[0] != self.size:
            self._dev_sigs = jnp.asarray(self.sigs)
            self._dev_valid = jnp.asarray(self.valid)
        return self._dev_sigs

    @property
    def device_valid(self) -> jnp.ndarray:
        self.device_sigs
        return self._dev_valid

    @property
    def device_band_keys(self) -> jnp.ndarray:
        """(N, n_bands) uint32 — every sequence's bucket key in every band
        (band layout only; a sequence occupies exactly ONE bucket per band).
        This is the duplicate-structure oracle of the fused self-join: a
        candidate pair is a cross-band duplicate iff the two rows agree in
        an earlier band (``repro.index.spgemm.spgemm_join_self_keys``)."""
        if self.layout != "band":
            raise ValueError("band keys are only defined for layout='band'")
        if (self._dev_band_keys is None
                or self._dev_band_keys.shape[0] != self.size):
            self._dev_band_keys = band_keys(
                self.device_sigs, self.cfg.f, self.bands,
                interleave=self.interleave, key_hash=self.key_hash)
        return self._dev_band_keys

    # ------------------------------------------------------------ build
    @classmethod
    def build(cls, cfg: LSHConfig, ref_ids, ref_lens, *,
              layout: str = "band", bands: int | None = None,
              interleave: bool = True,
              key_hash: str = "splitmix",
              n_shards: int = 1) -> "SignatureIndex":
        """Run job 1 (signature generation + validity) over the reference set."""
        with span("index_build", cat="lifecycle", n=len(ref_lens)):
            sl = ScalLoPS(cfg)
            sigs = np.asarray(sl.signatures(ref_ids, ref_lens))
            valid = np.asarray(sl.feature_counts(ref_ids, ref_lens)) > 0
            idx = cls(cfg, sigs, valid, layout=layout, bands=bands,
                      interleave=interleave, key_hash=key_hash,
                      n_shards=n_shards)
        idx._pipeline = sl
        return idx

    def add(self, ref_ids, ref_lens) -> None:
        """Incremental growth: signatures for the NEW rows only, appended as
        a pending segment and sealed (bucketed) lazily on the next
        probe/refresh/save. Resident segments are never re-bucketed; the
        merged table re-merges lazily for consumers that need it."""
        if self._pipeline is None:
            self._pipeline = ScalLoPS(self.cfg)
        sl = self._pipeline
        new_sigs = np.asarray(sl.signatures(ref_ids, ref_lens))
        new_valid = np.asarray(sl.feature_counts(ref_ids, ref_lens)) > 0
        if new_sigs.shape[0] == 0:
            return
        base = self.size
        self.sigs = np.concatenate([self.sigs, new_sigs], axis=0)
        self.valid = np.concatenate([self.valid, new_valid], axis=0)
        self._pending.append((new_sigs, new_valid, base))
        self._merged_stale = True
        self._partitions = {}       # full partitions derive from the merge

    def seal(self) -> None:
        """Seal pending rows into segments (bucket the new rows). Cheap
        relative to a rebuild: O(new rows), resident segments untouched."""
        if not self._pending:
            return
        with span("seal", cat="lifecycle", pending=len(self._pending),
                  epoch=len(self.segments)):
            while self._pending:
                sigs, valid, base = self._pending.pop(0)
                self.segments.append(seglib.build_segment(
                    sigs, valid, base, layout=self.layout, f=self.cfg.f,
                    d=self.cfg.d, bands=self.bands,
                    interleave=self.interleave, key_hash=self.key_hash))

    def _ensure_built(self) -> None:
        """Seal pending segments and materialize the merged bucket table."""
        self.seal()
        if not self._merged_stale and self._csr_np is not None:
            return
        if self.segments:
            self._csr_np = seglib.merge_band_csrs(
                [s.csr for s in self.segments])
        else:
            self._csr_np = [seglib._empty_csr() for _ in range(self.n_bands)]
        self._partitions = {}       # slabs derive from the fresh merge
        self._merged_stale = False

    def compact(self) -> None:
        """Fold every segment into one (the explicit reduce step).

        Probe results are identical before and after — compaction changes
        the storage shape, never the bucket table. Bumps ``generation`` so
        delta consumers (:class:`ShardedIndex`) re-place instead of
        stacking deltas on a base that no longer exists. Already-compact
        indexes (one sealed segment, nothing pending) are a no-op — no
        generation bump, so serving replicas skip the full re-place."""
        self.seal()
        if len(self.segments) == 1:
            return
        with span("compact_index", cat="lifecycle",
                  segments=len(self.segments), size=self.size):
            self._ensure_built()
            self.segments = [Segment(0, self.sigs, self.valid, self._csr_np)]
            self._pending = []
            self.generation += 1

    def partition(self, n_shards: int | None = None) -> "BucketPartition":
        """Shard-owned stacked CSR slabs (:mod:`repro.index.partition`) —
        the single stacking code path shared by the fused single-device
        probe (``n_shards=1``), the sharded serving ring, and the sharded
        self-join. Cached per shard count; invalidated on add/compact."""
        from .partition import BucketPartition
        self._ensure_built()
        n = int(n_shards if n_shards is not None else self.n_shards)
        part = self._partitions.get(n)
        if part is None:
            part = BucketPartition(self._csr_np, n, sigs=self.sigs)
            self._partitions[n] = part
        return part

    def delta_partition(self, n_shards: int, from_epoch: int):
        """Partition of just the segments sealed at/after ``from_epoch`` —
        what a serving replica ingests on refresh. Never touches the
        merged table; cost is O(delta entries)."""
        from .partition import BucketPartition
        self.seal()
        segs = self.segments[from_epoch:]
        if segs:
            csr = seglib.merge_band_csrs([s.csr for s in segs])
        else:
            csr = [seglib._empty_csr() for _ in range(self.n_bands)]
        return BucketPartition(csr, n_shards, sigs=self.sigs)

    # ------------------------------------------------------------ probing
    def query_keys(self, q_sigs) -> jnp.ndarray:
        """Per-band probe keys for a query batch: (n_bands, B) uint32."""
        q_sigs = jnp.asarray(q_sigs)
        if self.layout == "flip":
            return q_sigs[:, 0][None, :]
        return band_keys(q_sigs, self.cfg.f, self.bands,
                         interleave=self.interleave,
                         key_hash=self.key_hash).T

    def probe(self, q_sigs, *, cap: int):
        """Candidate generation: for each query, up to ``cap`` reference ids
        per band whose bucket key matches.

        Returns (cand (B, n_bands*cap) int32 with -1 padding — duplicates
        across bands allowed, consumers dedup, overflowed 0-d bool — True
        iff some matched bucket held more than ``cap`` entries, i.e.
        candidates were truncated and the caller should grow ``cap`` and
        retry).

        All bands probe in ONE jitted program over the stacked per-band
        CSR arrays (no per-band Python dispatch loop).
        """
        from .service import _probe_csr_fused  # jitted probe primitive
        self._ensure_built()
        qk = self.query_keys(q_sigs)
        keys_s, offs_s, ids_s = self.partition(1).probe_arrays(0)
        if keys_s.shape[1] == 0:           # no buckets at all (empty index)
            B = qk.shape[1]
            return (jnp.full((B, self.n_bands * cap), -1, jnp.int32),
                    jnp.zeros((), bool))
        cand, sizes = _probe_csr_fused(qk, keys_s, offs_s, ids_s, cap=cap)
        return cand, jnp.max(sizes) > cap

    # ------------------------------------------------------------ persistence
    def _meta(self) -> dict:
        return {
            "format": FORMAT_VERSION,
            "fingerprint": self.fingerprint,
            "cfg": dataclasses.asdict(self.cfg),
            "layout": self.layout,
            "bands": self.bands,
            "interleave": self.interleave,
            "key_hash": self.key_hash,
            "n_shards": self.n_shards,
            "n_refs": self.size,
        }

    def save(self, path: str | os.PathLike) -> int:
        """Persist the index; returns the number of segment files written.

        Paths ending in ``.npz`` write the legacy monolithic container
        (merged table, one file — what PR 1–4 produced). Any other path is
        a segment directory: manifest + per-segment files, and repeated
        saves append only the segments not on disk yet (O(delta) — the
        point of the append-only lifecycle).
        """
        if not seglib.is_segmented(path):
            self._ensure_built()
            payload = {
                "meta_json": np.frombuffer(
                    json.dumps(self._meta(), sort_keys=True).encode(),
                    dtype=np.uint8),
                "sigs": self.sigs,
                "valid": self.valid,
            }
            for b, (keys, offsets, ids) in enumerate(self._csr_np):
                payload[f"band{b}_keys"] = keys
                payload[f"band{b}_offsets"] = offsets
                payload[f"band{b}_ids"] = ids
            atomic_write(os.fspath(path),
                         lambda fh: np.savez_compressed(fh, **payload))
            return 1
        self.seal()                 # segments only — no merge needed
        return seglib.save_segmented(path, self._meta(), self.segments,
                                     self.n_bands)

    @classmethod
    def _check_meta(cls, meta: dict, expected_cfg: LSHConfig | None):
        """Shared fingerprint verification for both containers; returns the
        constructor kwargs."""
        cfg = LSHConfig(**meta["cfg"])
        layout, bands = meta["layout"], int(meta["bands"])
        interleave = bool(meta.get("interleave", True))
        # pre-key-hash indexes (PR 1/2) bucketed on raw band keys
        key_hash = meta.get("key_hash", "none")
        # pre-sharding indexes (PR 1-3) are 1-way partitions
        n_shards = int(meta.get("n_shards", 1))
        stored = meta["fingerprint"]
        recomputed = config_fingerprint(cfg, layout=layout, bands=bands,
                                        interleave=interleave,
                                        key_hash=key_hash,
                                        n_shards=n_shards)
        if stored != recomputed:
            raise IndexConfigMismatch(
                f"fingerprint {stored} does not match stored config "
                f"(expected {recomputed}) — corrupt or stale index")
        if expected_cfg is not None:
            want = config_fingerprint(expected_cfg, layout=layout,
                                      bands=bands, interleave=interleave,
                                      key_hash=key_hash,
                                      n_shards=n_shards)
            if want != stored:
                raise IndexConfigMismatch(
                    f"index fingerprint {stored} != {want} for the "
                    f"requested config; rebuild the index")
        return cfg, dict(layout=layout, bands=bands, interleave=interleave,
                         key_hash=key_hash, n_shards=n_shards)

    @classmethod
    def load(cls, path: str | os.PathLike,
             expected_cfg: LSHConfig | None = None, *,
             recover: bool = False) -> "SignatureIndex":
        """Load a persisted index; fails loudly on config mismatch.

        One entry point for both containers: segment directories load
        their manifest + segment files; ``.npz`` paths load the PR 1–4
        monolithic format as a single sealed segment (back-compat — the
        pre-key-hash and pre-sharding metadata defaults apply).

        If ``expected_cfg`` is given, its fingerprint must match the stored
        one — a stale index built under different LSH parameters raises
        :class:`IndexConfigMismatch` instead of silently serving wrong
        buckets.

        Damaged segment files raise a typed
        :class:`~repro.index.segments.CorruptSegment` naming the file;
        with ``recover=True`` the damaged tail is quarantined instead and
        the longest valid segment prefix is served, with the drop report
        on ``idx.recovery`` (see :func:`repro.index.segments.
        load_segmented`).
        """
        if seglib.is_segmented(path) and os.path.exists(
                seglib.manifest_path(path)):
            meta, segments, recovery = seglib.load_segmented(
                path, recover=recover)
            if meta.get("format") != FORMAT_VERSION:
                raise IndexConfigMismatch(
                    f"index format {meta.get('format')} != {FORMAT_VERSION}")
            cfg, kw = cls._check_meta(meta, expected_cfg)
            if segments:
                sigs = np.concatenate([s.sigs for s in segments], axis=0)
                valid = np.concatenate([s.valid for s in segments], axis=0)
            else:
                sigs = np.zeros((0, cfg.f // 32), np.uint32)
                valid = np.zeros((0,), bool)
            idx = cls(cfg, sigs, valid, **kw)
            idx._pending = []
            idx.segments = segments
            idx.recovery = recovery
            return idx
        try:
            z = np.load(path)
        except (OSError, EOFError, ValueError,
                zipfile.BadZipFile) as err:
            # the monolithic container has no prefix to fall back to —
            # a torn legacy npz is typed, named, and unrecoverable
            raise CorruptSegment(
                os.fspath(path),
                f"legacy index {path} is unreadable (truncated or torn "
                f"write): {type(err).__name__}: {err}") from err
        with z:
            meta = json.loads(bytes(z["meta_json"].tobytes()).decode())
            if meta.get("format") != FORMAT_VERSION:
                raise IndexConfigMismatch(
                    f"index format {meta.get('format')} != {FORMAT_VERSION}")
            cfg, kw = cls._check_meta(meta, expected_cfg)
            idx = cls(cfg, z["sigs"], z["valid"], **kw)
            csr = []
            for b in range(idx.n_bands):
                csr.append((z[f"band{b}_keys"], z[f"band{b}_offsets"],
                            z[f"band{b}_ids"]))
        # the monolithic table IS one sealed segment (ids are global,
        # base 0) — no re-bucketing, and the merged view is it
        idx._pending = []
        idx.segments = [Segment(0, idx.sigs, idx.valid, csr)]
        idx._csr_np = csr
        idx._merged_stale = False
        return idx
