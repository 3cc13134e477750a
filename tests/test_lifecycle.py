"""Segmented mutable-index lifecycle: append-only segments, delta refresh,
manifest persistence (incl. PR 1/3/4 legacy-format back-compat), delta
self-join, and the persistent family forest.

The one invariant everything here pins: a segmented index — however it was
grown, refreshed, persisted, or compacted — is BIT-EXACT with a
from-scratch rebuild over the concatenated corpus (probe results, pair
sets, family labels, overflow contracts)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.allpairs import (AllPairsConfig, FamilyForest, all_pairs_ingest,
                            all_pairs_search, forest_from_result,
                            lsh_delta_join, lsh_self_join, union_find)
from repro.core import LSHConfig, ScalLoPS
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import (QueryEngine, ServingConfig, ShardedIndex,
                         SignatureIndex)
from repro.index.service import topk_probe

CFG = LSHConfig(k=3, T=13, f=32, d=1)


@pytest.fixture(scope="module")
def data():
    return make_protein_sets(SyntheticProteinConfig(
        n_refs=120, n_homolog_queries=16, n_decoy_queries=16,
        ref_len_mean=90, ref_len_std=12, sub_rates=(0.04, 0.1), seed=77))


@pytest.fixture(scope="module")
def q_sigs(data):
    return ScalLoPS(CFG).signatures(data["query_ids"], data["query_lens"])


def _segmented(data, n_segments: int, **kw) -> SignatureIndex:
    """The corpus ingested in ``n_segments`` add() rounds."""
    n = len(data["ref_lens"])
    cuts = np.linspace(0, n, n_segments + 1).astype(int)
    idx = SignatureIndex.build(CFG, data["ref_ids"][:cuts[1]],
                               data["ref_lens"][:cuts[1]], **kw)
    for a, b in zip(cuts[1:-1], cuts[2:]):
        idx.add(data["ref_ids"][a:b], data["ref_lens"][a:b])
    return idx


# ------------------------------------------------------------ merged table
@pytest.mark.parametrize("n_segments", [1, 2, 3])
def test_segmented_probe_matches_rebuild(data, q_sigs, n_segments):
    """topk_probe over a segmented index == a from-scratch rebuild of the
    concatenated corpus, before AND after compact() — the acceptance grid's
    single-device arm (the sharded arm runs under forced devices below)."""
    full = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])
    seg = _segmented(data, n_segments)
    assert seg.epoch == n_segments
    want = topk_probe(full, q_sigs, k=6, cap=32)
    got = topk_probe(seg, q_sigs, k=6, cap=32)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the merged bucket table itself is bit-exact (stable linear merge ==
    # from-scratch sort), which is what makes every consumer agree
    full._ensure_built()
    for (k1, o1, i1), (k2, o2, i2) in zip(full._csr_np, seg._csr_np):
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(i1, i2)
    seg.compact()
    assert seg.epoch == 1
    after = topk_probe(seg, q_sigs, k=6, cap=32)
    for a, b in zip(want, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_add_does_not_rebucket_resident_segments(data):
    """The append-only contract: sealing a new segment leaves resident
    segment objects untouched (no invalidate-and-rebuild)."""
    idx = _segmented(data, 2)
    idx.seal()
    resident = idx.segments[0]
    rkeys = [k.copy() for k, _, _ in resident.csr]
    idx.add(data["ref_ids"][:10], data["ref_lens"][:10])
    idx.seal()
    assert idx.segments[0] is resident
    for (k, _, _), k0 in zip(resident.csr, rkeys):
        np.testing.assert_array_equal(k, k0)


# ------------------------------------------------------------ delta refresh
def test_sharded_delta_refresh_bitexact(data, q_sigs):
    """A serving replica ingests segment deltas via refresh() — no full
    reload — and stays bit-exact with the merged-table probe, including
    the grow-and-retry overflow contract and compaction."""
    idx = SignatureIndex.build(CFG, data["ref_ids"][:70],
                               data["ref_lens"][:70])
    sh = ShardedIndex(idx)
    sh.topk(q_sigs, k=6, cap=32)            # base placement served
    idx.add(data["ref_ids"][70:100], data["ref_lens"][70:100])
    idx.add(data["ref_ids"][100:], data["ref_lens"][100:])
    got = sh.topk(q_sigs, k=6, cap=32)
    assert sh._delta is not None, "expected a delta slab, not a re-place"
    assert sh.epoch == (1, 3)
    want = topk_probe(idx, q_sigs, k=6, cap=32)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))
    assert (got[2], got[3]) == (want[2], want[3])
    # tiny cap: the retry loop must see summed base+delta bucket sizes
    grown = sh.topk(q_sigs, k=6, cap=1)
    w2 = topk_probe(idx, q_sigs, k=6, cap=1)
    np.testing.assert_array_equal(grown[0], np.asarray(w2[0]))
    assert (grown[2], grown[3]) == (w2[2], w2[3])
    # serving-side compaction: identical results, delta folded away
    sh.compact()
    assert sh._delta is None
    after = sh.topk(q_sigs, k=6, cap=32)
    np.testing.assert_array_equal(after[0], got[0])
    np.testing.assert_array_equal(after[1], got[1])
    # index-side compaction bumps generation -> replica re-places
    idx.add(data["ref_ids"][:5], data["ref_lens"][:5])
    idx.compact()
    gen_before = sh._gen
    sh.topk(q_sigs, k=6, cap=32)
    assert sh._gen == gen_before + 1 and sh._delta is None


def test_sharded_refresh_auto_compacts_large_delta(data, q_sigs):
    """A delta that outgrows the base placement is folded in instead of
    carried (the carrying cost would exceed the re-place)."""
    idx = SignatureIndex.build(CFG, data["ref_ids"][:20],
                               data["ref_lens"][:20])
    sh = ShardedIndex(idx)
    sh.topk(q_sigs, k=4, cap=32)
    idx.add(data["ref_ids"][20:], data["ref_lens"][20:])    # 100 >> 20
    got = sh.topk(q_sigs, k=4, cap=32)
    assert sh._delta is None, "oversized delta should have re-placed"
    want = topk_probe(idx, q_sigs, k=4, cap=32)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))


def test_flip_layout_sharded_and_refreshed(data, q_sigs):
    """The flip layout partitions like any other table (n_bands == 1):
    sharded serving and the delta refresh hold bit-exact (the ROADMAP
    'shard_map probe for flip layout' item; n_shards > 1 runs in the
    forced-device subprocess of test_sharding.py)."""
    idx = SignatureIndex.build(CFG, data["ref_ids"][:80],
                               data["ref_lens"][:80], layout="flip")
    sh = ShardedIndex(idx)
    got = sh.topk(q_sigs, k=6, cap=64)
    want = topk_probe(idx, q_sigs, k=6, cap=64)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))
    idx.add(data["ref_ids"][80:], data["ref_lens"][80:])
    got = sh.topk(q_sigs, k=6, cap=64)
    assert sh._delta is not None
    want = topk_probe(idx, q_sigs, k=6, cap=64)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))


def test_engine_serves_across_live_refresh(data):
    """QueryEngine keeps serving while the index grows underneath it; the
    epoch counter surfaces in stats, and results are identical before and
    after compaction of the refreshed placement."""
    idx = SignatureIndex.build(CFG, data["ref_ids"][:70],
                               data["ref_lens"][:70])
    eng = QueryEngine(idx, ServingConfig(k=5), sharded=ShardedIndex(idx))
    eng.query_batch(data["query_ids"][:8], data["query_lens"][:8])
    assert eng.stats()["index_epoch"] == 1
    idx.add(data["ref_ids"][70:], data["ref_lens"][70:])
    a = eng.query_batch(data["query_ids"][:8], data["query_lens"][:8])
    assert eng.stats()["index_epoch"] == 2
    eng.sharded.compact()
    b = eng.query_batch(data["query_ids"][:8], data["query_lens"][:8])
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


# ------------------------------------------------------------ persistence
def test_segmented_save_appends_only_new_segments(tmp_path, data, q_sigs):
    """Repeated saves of a growing index write only the new segment files
    (O(delta) persistence); the loaded replica is bit-exact."""
    d = tmp_path / "idx"
    idx = SignatureIndex.build(CFG, data["ref_ids"][:60],
                               data["ref_lens"][:60])
    assert idx.save(d) == 1
    seg0 = d / "seg-g000-00000.npz"
    stamp = seg0.stat().st_mtime_ns
    idx.add(data["ref_ids"][60:], data["ref_lens"][60:])
    assert idx.save(d) == 1                 # ONLY the new segment
    assert seg0.stat().st_mtime_ns == stamp
    assert sorted(p.name for p in d.glob("seg-*.npz")) == \
        ["seg-g000-00000.npz", "seg-g000-00001.npz"]
    loaded = SignatureIndex.load(d, expected_cfg=CFG)
    assert loaded.epoch == 2
    want = topk_probe(idx, q_sigs, k=5, cap=256)
    got = topk_probe(loaded, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))


def test_segmented_compact_roundtrip(tmp_path, data, q_sigs):
    """save -> compact -> save -> load: one segment file remains, stale
    files are dropped, and probe results never move."""
    d = tmp_path / "idx"
    idx = _segmented(data, 3)
    idx.save(d)
    assert len(list(d.glob("seg-*.npz"))) == 3
    want = topk_probe(idx, q_sigs, k=5, cap=256)
    idx.compact()
    assert idx.save(d) == 1
    # the rewrite lands under a NEW write generation (crash mid-rewrite
    # can never clobber the files the old manifest points at) and the
    # stale generation is GC'd after the manifest commits
    assert sorted(p.name for p in d.glob("seg-*.npz")) == \
        ["seg-g001-00000.npz"]
    loaded = SignatureIndex.load(d, expected_cfg=CFG)
    got = topk_probe(loaded, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))
    np.testing.assert_array_equal(
        lsh_self_join(idx).pairs, lsh_self_join(loaded).pairs)


def test_manifest_rejects_stale_config(tmp_path, data):
    from repro.index import IndexConfigMismatch
    d = tmp_path / "idx"
    _segmented(data, 2).save(d)
    with pytest.raises(IndexConfigMismatch):
        SignatureIndex.load(d, expected_cfg=LSHConfig(k=4, T=22, f=32))


def test_save_detects_different_corpus_same_shape(tmp_path, data, q_sigs):
    """The append-only prefix check is CONTENT-aware: saving a different
    index (same config, same corpus shape) into an existing directory
    must rewrite it, never silently keep the stale files."""
    d = tmp_path / "idx"
    a = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])
    a.save(d)
    # same shapes, different content (rows reversed)
    b = SignatureIndex.build(CFG, data["ref_ids"][::-1],
                             np.ascontiguousarray(data["ref_lens"][::-1]))
    assert b.save(d) == 1                   # rewritten, not skipped
    loaded = SignatureIndex.load(d, expected_cfg=CFG)
    np.testing.assert_array_equal(loaded.sigs, b.sigs)
    got = topk_probe(loaded, q_sigs, k=5, cap=256)
    want = topk_probe(b, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_manifest_rejects_swapped_segment_file(tmp_path, data):
    """A segment file whose content disagrees with the manifest checksum
    fails loudly instead of serving wrong signature rows."""
    d = tmp_path / "idx"
    idx = SignatureIndex.build(CFG, data["ref_ids"][:60],
                               data["ref_lens"][:60])
    idx.add(data["ref_ids"][60:120], data["ref_lens"][60:120])
    idx.save(d)
    seg1 = d / "seg-g000-00001.npz"
    z = dict(np.load(seg1))
    z["sigs"] = z["sigs"][::-1].copy()      # same shape, different content
    np.savez_compressed(seg1, **z)
    with pytest.raises(ValueError, match="content hash"):
        SignatureIndex.load(d)


def test_manifest_rejects_reordered_segments(tmp_path, data):
    """Segments concatenate in manifest order while their CSR ids embed
    the stored base — a reordered/corrupt manifest must fail loudly, never
    serve wrong signature rows silently."""
    d = tmp_path / "idx"
    _segmented(data, 2).save(d)
    mpath = d / "manifest.json"
    m = json.loads(mpath.read_text())
    m["segments"] = m["segments"][::-1]
    mpath.write_text(json.dumps(m))
    with pytest.raises(ValueError, match="reordered or corrupt"):
        SignatureIndex.load(d)


def test_truncated_segment_raises_typed_error_naming_file(tmp_path, data):
    """A torn/truncated segment file raises CorruptSegment carrying the
    offending filename — the operator knows WHICH file to restore."""
    from repro.index.segments import CorruptSegment
    d = tmp_path / "idx"
    _segmented(data, 3).save(d)
    victim = d / "seg-g000-00001.npz"
    blob = victim.read_bytes()
    victim.write_bytes(blob[:len(blob) // 3])        # torn write of old
    with pytest.raises(CorruptSegment) as ei:
        SignatureIndex.load(d)
    assert "seg-g000-00001.npz" in ei.value.file
    assert "seg-g000-00001.npz" in str(ei.value)


def test_checksum_mismatch_is_typed_with_file(tmp_path, data):
    """The PR 5 checksum rejection is now a typed CorruptSegment (still a
    ValueError — older handlers keep working) that names the file."""
    from repro.index.segments import CorruptSegment
    d = tmp_path / "idx"
    _segmented(data, 2).save(d)
    seg1 = d / "seg-g000-00001.npz"
    z = dict(np.load(seg1))
    z["sigs"] = z["sigs"][::-1].copy()
    np.savez_compressed(seg1, **z)
    with pytest.raises(CorruptSegment) as ei:
        SignatureIndex.load(d)
    assert isinstance(ei.value, ValueError)
    assert "seg-g000-00001.npz" in ei.value.file


def test_recovery_quarantines_tail_serves_valid_prefix(tmp_path, data,
                                                       q_sigs):
    """load(recover=True) on a damaged middle segment quarantines it AND
    everything after it (later global ids assume the damaged rows exist),
    rewrites the manifest to the valid prefix, and serves that prefix
    bit-exact with a from-scratch rebuild of the same rows."""
    d = tmp_path / "idx"
    _segmented(data, 3).save(d)              # 3 segments: 40 rows each
    victim = d / "seg-g000-00001.npz"
    blob = victim.read_bytes()
    victim.write_bytes(blob[: len(blob) // 3])
    idx = SignatureIndex.load(d, recover=True)
    rec = idx.recovery
    assert rec is not None and "seg-g000-00001.npz" in rec["file"]
    assert rec["n_segments_dropped"] == 2    # the damaged one AND its tail
    assert rec["n_rows_served"] == idx.size == 40
    assert sorted(rec["quarantined"]) == ["seg-g000-00001.npz",
                                          "seg-g000-00002.npz"]
    for f in rec["quarantined"]:             # evidence moved, not deleted
        assert (d / "quarantine" / f).exists()
        assert not (d / f).exists()
    # the served prefix is bit-exact with a rebuild of those rows
    prefix = SignatureIndex.build(CFG, data["ref_ids"][:40],
                                  data["ref_lens"][:40])
    want = topk_probe(prefix, q_sigs, k=5, cap=64)
    got = topk_probe(idx, q_sigs, k=5, cap=64)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the rewritten manifest loads CLEAN now — recovery is durable
    again = SignatureIndex.load(d)
    assert again.recovery is None and again.size == 40


def test_legacy_npz_torn_write_is_typed(tmp_path, data):
    """A truncated monolithic .npz (no prefix to fall back to) raises a
    typed CorruptSegment naming the path instead of a bare zipfile/OSError
    from deep inside numpy."""
    from repro.index.segments import CorruptSegment
    p = tmp_path / "idx.npz"
    SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"]).save(p)
    blob = p.read_bytes()
    p.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(CorruptSegment) as ei:
        SignatureIndex.load(p)
    assert "idx.npz" in ei.value.file


def test_forest_generation_and_size_mismatch_typed(tmp_path, data):
    """A persisted family forest that does not belong to the index it is
    loaded for (stale generation, wrong corpus size, torn file) raises
    ForestMismatch naming the file — a stale forest silently mislabeling
    families is the failure this guards against."""
    from repro.allpairs import ForestMismatch
    fpath = tmp_path / "families.npz"
    forest = FamilyForest(12)
    forest.union_edges(np.array([[0, 1], [2, 3]]))
    forest.save(fpath, generation=2)
    ok = FamilyForest.load(fpath, expect_n=12, expect_generation=2)
    np.testing.assert_array_equal(ok.labels(), forest.labels())
    with pytest.raises(ForestMismatch) as ei:
        FamilyForest.load(fpath, expect_generation=3)
    assert "families.npz" in ei.value.file and "generation" in str(ei.value)
    with pytest.raises(ForestMismatch, match="stale forest"):
        FamilyForest.load(fpath, expect_n=99)
    blob = fpath.read_bytes()                # torn forest file: typed too
    fpath.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(ForestMismatch, match="unreadable"):
        FamilyForest.load(fpath)
    # pre-PR 8 files carry no metadata: load fine, skip the gen check
    np.savez_compressed(fpath, parent=forest.parent, size=forest._size)
    FamilyForest.load(fpath, expect_generation=7)


def test_compact_noop_when_already_compact(data):
    """compact() on a single-sealed-segment index must not bump the
    generation (a replica would pay a full re-place for zero change)."""
    idx = _segmented(data, 2)
    sh = ShardedIndex(idx)
    idx.compact()
    gen = idx.generation
    sh.topk(np.asarray(idx.sigs[:4]), k=3, cap=32)      # re-placed once
    idx.compact()
    assert idx.generation == gen
    # loading a legacy monolithic npz is already compact too
    assert len(idx.segments) == 1


def _doctor_npz(path, drop_keys):
    """Rewrite a monolithic npz's embedded meta WITHOUT the given keys —
    reproducing what PR 1/PR 3-era files actually contain (their
    fingerprints omitted those fields, so they stay self-consistent)."""
    z = dict(np.load(path))
    meta = json.loads(bytes(z["meta_json"].tobytes()).decode())
    for k in drop_keys:
        meta.pop(k, None)
    z["meta_json"] = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode(), dtype=np.uint8)
    np.savez_compressed(path, **z)


@pytest.mark.parametrize("era,kw,drop", [
    # PR 1/2 files: raw band keys, no key_hash or n_shards metadata
    ("pr1", dict(key_hash="none"), ["key_hash", "n_shards"]),
    # PR 3 files: splitmix key mixing, still pre-sharding
    ("pr3", dict(key_hash="splitmix"), ["n_shards"]),
    # PR 4 files: n_shards joined the metadata/fingerprint
    ("pr4", dict(key_hash="splitmix", n_shards=4), []),
])
def test_legacy_npz_formats_load(tmp_path, data, q_sigs, era, kw, drop):
    """Monolithic fixtures from every prior era load through the one
    entry point (as a single sealed segment) and probe bit-exact."""
    idx = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"], **kw)
    path = tmp_path / f"{era}.npz"
    idx.save(path)
    _doctor_npz(path, drop)
    loaded = SignatureIndex.load(path, expected_cfg=CFG)
    assert loaded.key_hash == kw.get("key_hash", "splitmix")
    assert loaded.n_shards == kw.get("n_shards", 1)
    assert loaded.epoch == 1
    want = topk_probe(idx, q_sigs, k=5, cap=256)
    got = topk_probe(loaded, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))
    # ...and a legacy index keeps growing through the segmented lifecycle
    loaded.add(data["query_ids"], data["query_lens"])
    assert loaded.epoch == 2
    d = tmp_path / f"{era}_grown"
    loaded.save(d)
    re = SignatureIndex.load(d, expected_cfg=CFG)
    a = topk_probe(loaded, q_sigs, k=5, cap=256)
    b = topk_probe(re, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ------------------------------------------------------------ delta join
@pytest.mark.parametrize("d_filter", [None, CFG.d])
@pytest.mark.parametrize("rounds", [1, 2])
def test_delta_join_union_equals_full(data, d_filter, rounds):
    """old pairs ∪ delta pairs == from-scratch self-join over the grown
    corpus (same dedup, filter, and sort order), with every delta pair
    touching at least one new row."""
    n = len(data["ref_lens"])
    base = n - 40
    idx = SignatureIndex.build(CFG, data["ref_ids"][:base],
                               data["ref_lens"][:base])
    old = lsh_self_join(idx, d=d_filter)
    cuts = np.linspace(base, n, rounds + 1).astype(int)
    for a, b in zip(cuts[:-1], cuts[1:]):
        idx.add(data["ref_ids"][a:b], data["ref_lens"][a:b])
    delta = lsh_delta_join(idx, base_size=base, d=d_filter)
    assert (delta.pairs[:, 1] >= base).all()
    full = lsh_self_join(
        SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"]),
        d=d_filter)
    union = np.concatenate([old.pairs, delta.pairs], axis=0)
    union = union[np.lexsort((union[:, 1], union[:, 0]))]
    np.testing.assert_array_equal(union, full.pairs)


def test_delta_join_boundary_and_empty(data):
    idx = SignatureIndex.build(CFG, data["ref_ids"][:60],
                               data["ref_lens"][:60])
    idx.add(data["ref_ids"][60:], data["ref_lens"][60:])
    with pytest.raises(ValueError):
        lsh_delta_join(idx, base_size=61)   # not a segment boundary
    empty = lsh_delta_join(idx, base_size=idx.size)
    assert empty.n_candidates == 0 and empty.n_rows == idx.size


# ------------------------------------------------------------ family forest
def test_forest_incremental_equals_scratch():
    rng = np.random.default_rng(3)
    n = 200
    edges = np.stack([rng.integers(0, n, 300),
                      rng.integers(0, n, 300)], axis=1)
    want = union_find(n, edges)
    forest = FamilyForest(120)
    forest.union_edges(edges[(edges < 120).all(axis=1)][:50])
    forest.grow(n)
    mask = np.ones(len(edges), bool)        # replay the rest in odd order
    mask[np.flatnonzero((edges < 120).all(axis=1))[:50]] = False
    forest.union_edges(edges[mask][::-1])
    np.testing.assert_array_equal(forest.labels(), want)


def test_forest_roundtrip_and_shrink(tmp_path):
    forest = FamilyForest(10)
    forest.union_edges(np.array([[0, 3], [3, 7], [1, 2]]))
    p = tmp_path / "families.npz"
    forest.save(p)
    loaded = FamilyForest.load(p)
    np.testing.assert_array_equal(loaded.labels(), forest.labels())
    loaded.grow(12)
    assert loaded.n == 12
    with pytest.raises(ValueError):
        loaded.grow(5)


def test_ingest_families_equal_scratch(data):
    """End-to-end incremental clustering: index.add + delta join + delta
    scoring + forest union == all_pairs_search over the grown corpus."""
    ids = np.asarray(data["ref_ids"], np.int8)
    lens = np.asarray(data["ref_lens"], np.int32)
    n = len(lens)
    base = n - 40
    cfg = AllPairsConfig(lsh=CFG)
    res = all_pairs_search(ids[:base], lens[:base], cfg)
    forest = forest_from_result(res)
    ing = all_pairs_ingest(ids, lens, base, cfg, index=res.index,
                           forest=forest)
    scratch = all_pairs_search(ids, lens, cfg)
    np.testing.assert_array_equal(ing.labels, scratch.families.labels)


# ------------------------------------------------- sharded grid (forced dev)
_SUBPROCESS = """
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
from jax.sharding import Mesh

from repro.core import LSHConfig, ScalLoPS
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import ShardedIndex, SignatureIndex
from repro.index.service import topk_probe

data = make_protein_sets(SyntheticProteinConfig(
    n_refs=160, n_homolog_queries=16, n_decoy_queries=16,
    ref_len_mean=90, ref_len_std=12, sub_rates=(0.04, 0.1), seed=51))
cfg = LSHConfig(k=3, T=13, f=32, d=1)
q = ScalLoPS(cfg).signatures(data["query_ids"], data["query_lens"])
n = len(data["ref_lens"])

# the acceptance grid: every (n_segments, n_shards), bit-exact with a
# from-scratch rebuild before and after compaction, through the real
# shard_map/ppermute delta ring
full = SignatureIndex.build(cfg, data["ref_ids"], data["ref_lens"])
want = topk_probe(full, q, k=6, cap=32)
for n_segments in (2, 3):
    # majority-resident splits: the delta must stay smaller than the base
    # or refresh() (correctly) auto-compacts instead of carrying it
    cuts = np.concatenate(
        [[0], np.linspace(100, n, n_segments).astype(int)])
    for n_shards in (1, 2, 4):
        idx = SignatureIndex.build(cfg, data["ref_ids"][:cuts[1]],
                                   data["ref_lens"][:cuts[1]])
        sh = ShardedIndex(idx, Mesh(np.array(jax.devices()[:n_shards]),
                                    ("data",)))
        sh.topk(q, k=6, cap=32)             # base placement
        for a, b in zip(cuts[1:-1], cuts[2:]):
            idx.add(data["ref_ids"][a:b], data["ref_lens"][a:b])
        got = sh.topk(q, k=6, cap=32)       # delta refresh path
        assert sh._delta is not None, (n_segments, n_shards)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        sh.compact()
        assert sh._delta is None
        got = sh.topk(q, k=6, cap=32)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
print("GRID-EXACT")
"""


@pytest.mark.slow
def test_lifecycle_grid_forced_four_devices():
    """(n_segments, n_shards) acceptance grid under XLA-forced 4 host
    devices: the real ppermute ring probes base+delta slabs bit-exact
    with the from-scratch rebuild, before and after compaction."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "GRID-EXACT" in out.stdout, (out.stdout, out.stderr)
