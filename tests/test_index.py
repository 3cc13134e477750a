"""repro.index: store (build/persist/add), shard (fan-out), service (top-k,
overflow retry), plus the q_valid/r_valid masking branch of ScalLoPS.search."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import LSHConfig, ScalLoPS
from repro.core.join import pairs_to_set
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import (IndexConfigMismatch, QueryEngine, ServingConfig,
                         ShardedIndex, SignatureIndex)
from repro.index.service import topk_dense, topk_probe

CFG = LSHConfig(k=3, T=13, f=32, d=1, max_pairs=1 << 14)


@pytest.fixture(scope="module")
def data():
    return make_protein_sets(SyntheticProteinConfig(
        n_refs=96, n_homolog_queries=24, n_decoy_queries=24,
        ref_len_mean=100, ref_len_std=15, sub_rates=(0.03, 0.1), seed=17))


@pytest.fixture(scope="module")
def index(data):
    return SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])


@pytest.fixture(scope="module")
def q_sigs(data):
    return ScalLoPS(CFG).signatures(data["query_ids"], data["query_lens"])


def _brute_dists(q_sigs, index):
    q = np.asarray(q_sigs)
    r = index.sigs
    dist = np.zeros((len(q), len(r)), np.int32)
    for w in range(q.shape[1]):
        x = q[:, w][:, None] ^ r[:, w][None, :]
        dist += np.vectorize(lambda v: bin(int(v)).count("1"))(x)
    dist[:, ~index.valid] = 1 << 30
    return dist


# ---------------------------------------------------------------- store
def test_dense_topk_matches_bruteforce(index, q_sigs):
    nid, nd = topk_dense(index, q_sigs, k=5)
    dist = _brute_dists(q_sigs, index)
    want = np.sort(dist, axis=1)[:, :5]
    nd_np = np.asarray(nd).astype(np.int64)
    nd_np[nd_np < 0] = 1 << 30
    np.testing.assert_array_equal(nd_np, np.minimum(want, 1 << 30))


def test_probe_finds_all_neighbors_within_d(index, q_sigs):
    """Pigeonhole guarantee: every reference within Hamming d must surface
    in the probe top-k (k large enough to hold them all)."""
    dist = _brute_dists(q_sigs, index)
    k = int((dist <= CFG.d).sum(axis=1).max()) + 1
    nid, nd, *_ = topk_probe(index, q_sigs, k=k, cap=256)
    nid, nd = np.asarray(nid), np.asarray(nd)
    for i in range(dist.shape[0]):
        want = set(np.nonzero(dist[i] <= CFG.d)[0])
        got = set(nid[i][(nd[i] >= 0) & (nd[i] <= CFG.d)])
        assert got == want, f"query {i}: {got} != {want}"


def test_flip_layout_matches_flip_join(data, index, q_sigs):
    """flip-layout probe == the paper-faithful flip_join pair set within d."""
    from repro.core.join import flip_join
    idxf = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"],
                                layout="flip")
    dist = _brute_dists(q_sigs, idxf)
    k = int((dist <= CFG.d).sum(axis=1).max()) + 1
    nid, nd, *_ = topk_probe(idxf, q_sigs, k=k, cap=256)
    nid, nd = np.asarray(nid), np.asarray(nd)
    pairs, _ = flip_join(jnp.asarray(q_sigs), jnp.asarray(idxf.sigs),
                         f=CFG.f, d=CFG.d, max_pairs=1 << 14)
    want = pairs_to_set(pairs)
    want = {(q, r) for q, r in want if idxf.valid[r]}
    got = {(i, int(r)) for i in range(nid.shape[0])
           for r, dd in zip(nid[i], nd[i]) if r >= 0 and 0 <= dd <= CFG.d}
    assert got == want


def test_persistence_roundtrip_exact(tmp_path, index, q_sigs):
    path = tmp_path / "idx.npz"
    index.save(path)
    loaded = SignatureIndex.load(path, expected_cfg=CFG)
    a_ids, a_d, *_ = topk_probe(index, q_sigs, k=7, cap=128)
    b_ids, b_d, *_ = topk_probe(loaded, q_sigs, k=7, cap=128)
    np.testing.assert_array_equal(np.asarray(a_ids), np.asarray(b_ids))
    np.testing.assert_array_equal(np.asarray(a_d), np.asarray(b_d))


def test_load_rejects_stale_config(tmp_path, index):
    path = tmp_path / "idx.npz"
    index.save(path)
    with pytest.raises(IndexConfigMismatch):
        SignatureIndex.load(path, expected_cfg=LSHConfig(k=4, T=22, f=32))
    # serving-time knobs must NOT invalidate the index
    compatible = LSHConfig(k=CFG.k, T=CFG.T, f=CFG.f, d=CFG.d,
                           max_pairs=123, join_method="band")
    SignatureIndex.load(path, expected_cfg=compatible)


def test_incremental_add_matches_full_build(data, index, q_sigs):
    half = SignatureIndex.build(CFG, data["ref_ids"][:48],
                                data["ref_lens"][:48])
    half.add(data["ref_ids"][48:], data["ref_lens"][48:])
    assert half.size == index.size
    a_ids, a_d, *_ = topk_probe(index, q_sigs, k=5, cap=256)
    b_ids, b_d, *_ = topk_probe(half, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(a_d), np.asarray(b_d))


def test_add_then_save_roundtrips(tmp_path, data, q_sigs):
    half = SignatureIndex.build(CFG, data["ref_ids"][:48],
                                data["ref_lens"][:48])
    half.add(data["ref_ids"][48:], data["ref_lens"][48:])
    path = tmp_path / "grown.npz"
    half.save(path)  # forces the deferred re-sort
    loaded = SignatureIndex.load(path)
    a = topk_probe(half, q_sigs, k=5, cap=256)
    b = topk_probe(loaded, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ---------------------------------------------------------------- search mask
def test_search_valid_masking_drops_pairs(q_sigs, index):
    """q_valid/r_valid branch: pairs touching invalid rows are dropped and
    the count reflects the mask."""
    sl = ScalLoPS(CFG)
    r_sigs = jnp.asarray(index.sigs)
    full = sl.search(q_sigs, r_sigs)
    assert not bool(full.overflowed)
    base = pairs_to_set(full.pairs)
    assert base, "need some pairs for a meaningful mask test"
    drop_q = {q for q, _ in base if q % 2 == 0}
    qv = np.ones(np.asarray(q_sigs).shape[0], bool)
    qv[list(drop_q)] = False
    rv = np.ones(index.size, bool)
    drop_r = {r for _, r in base if r % 3 == 0}
    rv[list(drop_r)] = False
    res = sl.search(q_sigs, r_sigs, q_valid=qv, r_valid=rv)
    got = pairs_to_set(res.pairs)
    want = {(q, r) for q, r in base if qv[q] and rv[r]}
    assert got == want
    assert int(res.count) == len(want)


def test_search_overflow_flag(q_sigs, index):
    sl = ScalLoPS(CFG)
    r_sigs = jnp.asarray(index.sigs)
    full = sl.search(q_sigs, r_sigs)
    n = int(full.count)
    assert n > 2
    small = sl.search(q_sigs, r_sigs, max_pairs=2)
    assert bool(small.overflowed) and int(small.count) == n
    grown = sl.search(q_sigs, r_sigs, max_pairs=2 * n)
    assert not bool(grown.overflowed)


@pytest.mark.parametrize("method", ["flip", "band", "dense"])
def test_search_overflow_flag_all_joins(method):
    """Regression: band_join's candidate buffer can truncate *before* the
    final count, so count alone can look <= max_pairs while pairs were
    lost — overflowed must still be True (8x8 identical sigs, 64 pairs)."""
    sl = ScalLoPS(LSHConfig(k=3, T=13, f=32, d=0, join_method=method))
    sigs = jnp.tile(jnp.uint32([[0x12345678]]), (8, 1))
    res = sl.search(sigs, sigs, max_pairs=16)
    assert bool(res.overflowed)
    big = sl.search(sigs, sigs, max_pairs=256)
    assert not bool(big.overflowed) and int(big.count) == 64


# ---------------------------------------------------------------- service
def test_engine_overflow_grow_and_retry(data, index):
    eng = QueryEngine(index, ServingConfig(k=5, mode="probe", probe_cap=1))
    nid, nd = eng.query_batch(data["query_ids"], data["query_lens"])
    assert eng._probe_cap > 1          # capacity grew on overflow
    dense = QueryEngine(index, ServingConfig(k=5, mode="dense"))
    nid2, nd2 = dense.query_batch(data["query_ids"], data["query_lens"])
    # within-d neighbors agree between probe (grown) and dense paths
    for i in range(nid.shape[0]):
        a = set(nid[i][(nd[i] >= 0) & (nd[i] <= CFG.d)])
        b = set(nid2[i][(nd2[i] >= 0) & (nd2[i] <= CFG.d)])
        assert a == b


def test_engine_queue_and_invalid_queries(data, index):
    eng = QueryEngine(index, ServingConfig(k=3, max_batch=8))
    eng.submit("AAA")                  # k=3 -> single low-complexity shingle
    eng.submit("MDESFGLLLESMQARIEELNDVLRLINKWLRSTDAAQ")
    out = eng.flush()
    assert len(out) == 2 and eng.pending() == 0
    s = eng.stats()
    assert s["n_queries"] == 2 and s["n_batches"] == 1 and s["qps"] > 0


def test_engine_search_pairs_grows_capacity(data, index):
    eng = QueryEngine(index, ServingConfig(k=3))
    res = eng.search_pairs(data["query_ids"], data["query_lens"],
                           max_pairs=2)
    assert not bool(res.overflowed)    # grew until nothing truncated
    assert int(res.count) == len(pairs_to_set(res.pairs))


def test_engine_rerank_lq_quantized_no_retrace(data, index):
    """Two batches whose raw widths share a ladder rung must reuse ONE
    compiled gather+DP program (Lq is quantized to len_quantum)."""
    from repro.align.smith_waterman import sw_gather_scores
    eng = QueryEngine(index, ServingConfig(k=3, rerank=True),
                      ref_seqs=(data["ref_ids"], data["ref_lens"]))
    qi, ql = data["query_ids"], data["query_lens"]
    eng.query_batch(qi[:4, :70], np.minimum(ql[:4], 70))
    n1 = sw_gather_scores._cache_size()
    eng.query_batch(qi[:4, :90], np.minimum(ql[:4], 90))   # same 128 rung
    assert sw_gather_scores._cache_size() == n1
    eng.query_batch(qi[:4, :150], np.minimum(ql[:4], 150))  # new 192 rung
    assert sw_gather_scores._cache_size() == n1 + 1


def test_engine_rerank_reorders_by_sw(data, index):
    eng = QueryEngine(index, ServingConfig(k=3, rerank=True),
                      ref_seqs=(data["ref_ids"], data["ref_lens"]))
    nid, nd = eng.query_batch(data["query_ids"][:4], data["query_lens"][:4])
    assert nid.shape == (4, 3)
    # valid slots stay ahead of -1 padding after the reorder
    for row in nid:
        seen_invalid = False
        for v in row:
            if v < 0:
                seen_invalid = True
            else:
                assert not seen_invalid


# ---------------------------------------------------------------- shard
def test_sharded_single_device_matches_probe(index, q_sigs):
    """The bucket-sharded ring at n_shards=1 is bit-exact with topk_probe
    (same candidates, same tie-breaks, same overflow contract)."""
    sh = ShardedIndex(index)           # 1 CPU device in the main process
    nid, nd, cap, tr = sh.topk(q_sigs, k=5, cap=256)
    want_id, want_d, want_cap, want_tr = topk_probe(index, q_sigs, k=5,
                                                    cap=256)
    np.testing.assert_array_equal(nid, np.asarray(want_id))
    np.testing.assert_array_equal(nd, np.asarray(want_d))
    assert (cap, tr) == (want_cap, want_tr)


def test_sharded_grow_and_retry(index, q_sigs):
    """A tiny cap must grow until no matched bucket truncates, landing on
    the same results as a comfortably large cap."""
    sh = ShardedIndex(index)
    nid, nd, cap, tr = sh.topk(q_sigs, k=5, cap=1)
    assert cap > 1 and not tr
    big_id, big_d, *_ = sh.topk(q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(nid, big_id)
    np.testing.assert_array_equal(nd, big_d)


def test_sharded_engine_path_matches_probe_engine(data, index):
    """QueryEngine served through a ShardedIndex == the probe engine."""
    probe_eng = QueryEngine(index, ServingConfig(k=5, mode="probe"))
    a_id, a_d = probe_eng.query_batch(data["query_ids"], data["query_lens"])
    sh_eng = QueryEngine(index, ServingConfig(k=5), sharded=ShardedIndex(index))
    b_id, b_d = sh_eng.query_batch(data["query_ids"], data["query_lens"])
    np.testing.assert_array_equal(a_id, b_id)
    np.testing.assert_array_equal(a_d, b_d)


@pytest.mark.slow
def test_sharded_multi_device_matches_probe():
    """4 host devices in a subprocess (XLA flag must precede jax import)."""
    code = """
import numpy as np
from repro.core import LSHConfig, ScalLoPS
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import ShardedIndex, SignatureIndex
from repro.index.service import topk_probe

data = make_protein_sets(SyntheticProteinConfig(
    n_refs=50, n_homolog_queries=8, n_decoy_queries=8,
    ref_len_mean=80, ref_len_std=10, sub_rates=(0.05,), seed=23))
cfg = LSHConfig(k=3, T=13, f=32, d=1)
idx = SignatureIndex.build(cfg, data["ref_ids"], data["ref_lens"])
q = ScalLoPS(cfg).signatures(data["query_ids"], data["query_lens"])
sh = ShardedIndex(idx)
assert sh.n_shards == 4
nid, nd, cap, tr = sh.topk(q, k=5, cap=256)
want_id, want_d, *_ = topk_probe(idx, q, k=5, cap=256)
np.testing.assert_array_equal(nid, np.asarray(want_id))
np.testing.assert_array_equal(nd, np.asarray(want_d))
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=
                         os.path.dirname(os.path.dirname(__file__)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
