"""Bucket-partition layer + sharded consumers: ownership/slab invariants,
probe top-k and self-join pair-set equality for n_shards in {1, 2, 4}
(in-process via the vmap path, and under 4 forced host devices in a
subprocess for the real shard_map/ppermute programs), add() re-placement,
and save->load round-trip of a sharded index."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.allpairs import lsh_self_join
from repro.core import LSHConfig, ScalLoPS
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import (BucketPartition, ShardedIndex, SignatureIndex,
                         bucket_owners, config_fingerprint)
from repro.index.service import topk_probe

CFG = LSHConfig(k=3, T=13, f=32, d=1)


@pytest.fixture(scope="module")
def data():
    return make_protein_sets(SyntheticProteinConfig(
        n_refs=120, n_homolog_queries=20, n_decoy_queries=20,
        ref_len_mean=90, ref_len_std=12, sub_rates=(0.04, 0.1), seed=31))


@pytest.fixture(scope="module")
def index(data):
    return SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])


@pytest.fixture(scope="module")
def q_sigs(data):
    return ScalLoPS(CFG).signatures(data["query_ids"], data["query_lens"])


# ---------------------------------------------------------------- partition
@pytest.mark.parametrize("n", [1, 2, 4, 7])
def test_partition_buckets_are_whole_and_exhaustive(index, n):
    """Every bucket lands on exactly the shard mix32(key) % n owns, intact:
    the union of shard sub-CSRs is the original bucket table."""
    index._ensure_built()
    part = index.partition(n)
    assert part.n_shards == n
    for b, (keys, offsets, ids) in enumerate(index._csr_np):
        own = bucket_owners(keys, n)
        seen_keys, seen_members = [], {}
        for s in range(n):
            skeys, soffs, sids = part.shards[s][b]
            np.testing.assert_array_equal(own[np.isin(keys, skeys)], s)
            for u, key in enumerate(skeys):
                seen_keys.append(int(key))
                seen_members[int(key)] = sids[soffs[u]:soffs[u + 1]]
        assert sorted(seen_keys) == sorted(int(k) for k in keys)
        for u, key in enumerate(keys):
            np.testing.assert_array_equal(
                seen_members[int(key)], ids[offsets[u]:offsets[u + 1]])
    # pair totals sum to the unsharded total
    sizes = [np.diff(o).astype(np.int64) for _, o, _ in index._csr_np]
    want = sum(int((s * (s - 1) // 2).sum()) for s in sizes)
    assert int(part.pair_totals.sum()) == want


def test_partition_single_shard_slab_matches_probe_layout(index):
    """The 1-way partition IS the single-device probe layout (one stacking
    code path): shard 0's slab holds every band's full CSR."""
    index._ensure_built()
    part = index.partition(1)
    keys_s, offs_s, ids_s = (np.asarray(a) for a in part.device_slabs())
    assert keys_s.shape[0] == 1
    for b, (keys, offsets, ids) in enumerate(index._csr_np):
        u, e = len(keys), len(ids)
        np.testing.assert_array_equal(keys_s[0, b, :u], keys)
        np.testing.assert_array_equal(offs_s[0, b, :u + 1], offsets)
        np.testing.assert_array_equal(ids_s[0, b, :e], ids)


def test_partition_cache_invalidated_by_add(data, index):
    half = SignatureIndex.build(CFG, data["ref_ids"][:60],
                                data["ref_lens"][:60])
    p_before = half.partition(2)
    half.add(data["ref_ids"][60:], data["ref_lens"][60:])
    p_after = half.partition(2)
    assert p_after is not p_before
    assert int(p_after.n_entries.sum()) > int(p_before.n_entries.sum())


# ----------------------------------------------------------- vmap fallbacks
@pytest.mark.parametrize("n", [2, 4])
def test_selfjoin_sharded_pair_set_identical_inprocess(index, n):
    """n-way sharded emission (vmap path on one device) produces the
    bit-identical pair arrays, with and without the Hamming filter."""
    base = lsh_self_join(index)
    got = lsh_self_join(index, n_shards=n)
    np.testing.assert_array_equal(base.pairs, got.pairs)
    np.testing.assert_array_equal(base.indptr, got.indptr)
    base_d = lsh_self_join(index, d=CFG.d)
    got_d = lsh_self_join(index, d=CFG.d, n_shards=n)
    np.testing.assert_array_equal(base_d.pairs, got_d.pairs)


def test_selfjoin_uses_index_default_shards(data):
    """An index built with n_shards=2 self-joins through the 2-way
    partition by default — same pairs as the explicit override."""
    idx2 = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"],
                                n_shards=2)
    idx1 = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])
    np.testing.assert_array_equal(lsh_self_join(idx2).pairs,
                                  lsh_self_join(idx1).pairs)


# -------------------------------------------------------- skew-bounded caps
def test_selfjoin_skew_bounded_caps(data):
    """One degenerate bucket no longer inflates every shard's emission
    buffer: per-shard caps follow per-shard demand (ragged host merge),
    and the pair arrays are unchanged."""
    from repro.allpairs.selfjoin import _shard_caps
    # 40 copies of one sequence -> one degenerate bucket on one shard
    ids = np.concatenate([data["ref_ids"][:1].repeat(40, axis=0),
                          data["ref_ids"]], axis=0)
    lens = np.concatenate([data["ref_lens"][:1].repeat(40),
                           data["ref_lens"]])
    idx = SignatureIndex.build(CFG, ids, lens)
    base = lsh_self_join(idx)
    for n in (2, 4):
        caps = _shard_caps(idx.partition(n))
        # skewed demand: the degenerate shard's cap dominates, the others
        # stay at their own (much smaller) demand
        assert len(set(caps.tolist())) > 1, caps
        assert int(caps.sum()) < n * int(caps.max())
        got = lsh_self_join(idx, n_shards=n)
        np.testing.assert_array_equal(base.pairs, got.pairs)
        np.testing.assert_array_equal(base.indptr, got.indptr)
    # a non-pow2 max_grow between the true demand and its quantized buffer
    # size must not raise: overflow is judged on TRUE demand, quantization
    # only sizes buffers
    need = int(idx.partition(1).pair_totals.max())
    from repro.util import next_pow2
    assert next_pow2(need) > need + 1       # the quantized cap exceeds it
    lsh_self_join(idx, max_grow=need + 1)


def test_shard_caps_quantized_pow2(data):
    from repro.allpairs.selfjoin import _shard_caps
    from repro.util import next_pow2
    assert [next_pow2(x) for x in (0, 1, 2, 3, 65)] == [0, 1, 2, 4, 128]
    caps = _shard_caps(SignatureIndex.build(
        CFG, data["ref_ids"], data["ref_lens"]).partition(4))
    assert all(c == 0 or c == next_pow2(c) for c in caps.tolist())


# ------------------------------------------------------- jit-cache keying
def test_emit_program_cache_survives_fresh_mesh(data):
    """Regression (ROADMAP PR 4 trap): the sharded emission program is
    cached by DEVICE TUPLE, so constructing a new-but-equal Mesh per call
    resolves to the identical jitted program — no silent recompile."""
    import jax
    from jax.sharding import Mesh
    from repro.allpairs.selfjoin import (_emit_sharded_cached,
                                         _emit_sharded_fn)
    m1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    m2 = Mesh(np.array(jax.devices()[:1]), ("data",))
    size0 = _emit_sharded_cached.cache_info().currsize
    f1 = _emit_sharded_fn(m1, "data", 16)
    f2 = _emit_sharded_fn(m2, "data", 16)
    assert f1 is f2
    assert _emit_sharded_cached.cache_info().currsize == size0 + 1


def test_wave_fns_cache_keyed_by_device_tuple():
    """Regression (same PR 4 trap, wave side): the SPMD wave programs in
    allpairs.tiles are cached by DEVICE TUPLE — not a bare device count —
    so repeated calls with the same devices share one compiled program,
    and a different device subset cannot alias a stale entry."""
    import jax
    from repro.allpairs.tiles import _sharded_wave_fns
    devs = tuple(jax.devices()[:1])
    size0 = _sharded_wave_fns.cache_info().currsize
    f1 = _sharded_wave_fns(devs)
    f2 = _sharded_wave_fns(tuple(jax.devices()[:1]))    # fresh tuple, same devs
    assert f1 is f2
    assert _sharded_wave_fns.cache_info().currsize == size0 + 1
    # the key is the devices themselves: hashable, and a list (unhashable,
    # the bug a bare-count key invites back) is rejected loudly
    with pytest.raises(TypeError):
        _sharded_wave_fns(list(jax.devices()[:1]))


# ---------------------------------------------------------------- persistence
def test_sharded_index_roundtrip_and_fingerprint(tmp_path, data, q_sigs):
    idx = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"],
                               n_shards=4)
    # n_shards is part of the fingerprint (and omitted when 1 — the
    # pre-sharding fingerprint stays valid)
    assert idx.fingerprint != config_fingerprint(
        CFG, layout=idx.layout, bands=idx.bands, key_hash=idx.key_hash)
    path = tmp_path / "sharded.npz"
    idx.save(path)
    loaded = SignatureIndex.load(path, expected_cfg=CFG)
    assert loaded.n_shards == 4 and loaded.fingerprint == idx.fingerprint
    a = topk_probe(idx, q_sigs, k=5, cap=256)
    b = topk_probe(loaded, q_sigs, k=5, cap=256)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(lsh_self_join(idx).pairs,
                                  lsh_self_join(loaded).pairs)


# ------------------------------------------------------- forced 4 devices
_SUBPROCESS = """
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
from jax.sharding import Mesh

from repro.allpairs import WaveConfig, lsh_self_join, score_pairs
from repro.core import LSHConfig, ScalLoPS
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import ShardedIndex, SignatureIndex
from repro.index.service import topk_probe

data = make_protein_sets(SyntheticProteinConfig(
    n_refs=150, n_homolog_queries=16, n_decoy_queries=16,
    ref_len_mean=90, ref_len_std=12, sub_rates=(0.04, 0.1), seed=41))
cfg = LSHConfig(k=3, T=13, f=32, d=1)
idx = SignatureIndex.build(cfg, data["ref_ids"], data["ref_lens"])
sl = ScalLoPS(cfg)
q = sl.signatures(data["query_ids"], data["query_lens"])

# --- probe top-k identical for n_shards in {1, 2, 4} (bit-exact, real
# shard_map ring on distinct mesh sizes)
want_id, want_d, want_cap, want_tr = topk_probe(idx, q, k=6, cap=32)
want_id, want_d = np.asarray(want_id), np.asarray(want_d)
for n in (1, 2, 4):
    sh = ShardedIndex(idx, Mesh(np.array(jax.devices()[:n]), ("data",)))
    nid, nd, cap, tr = sh.topk(q, k=6, cap=32)
    np.testing.assert_array_equal(nid, want_id)
    np.testing.assert_array_equal(nd, want_d)
    assert (cap, tr) == (want_cap, want_tr), (n, cap, tr)
    # ragged batch (B % n != 0): padded query rows must not perturb
    # results OR the overflow contract
    r_id, r_d, r_cap, r_tr = sh.topk(q[:29], k=6, cap=32)
    w_id, w_d, w_cap, w_tr = topk_probe(idx, q[:29], k=6, cap=32)
    np.testing.assert_array_equal(r_id, np.asarray(w_id))
    np.testing.assert_array_equal(r_d, np.asarray(w_d))
    assert (r_cap, r_tr) == (w_cap, w_tr), (n, r_cap, r_tr)
print("PROBE-EXACT")

# --- self-join pair set identical for n_shards in {1, 2, 4} (shard_map)
base = lsh_self_join(idx, d=cfg.d)
for n in (2, 4):
    got = lsh_self_join(idx, d=cfg.d, n_shards=n)
    np.testing.assert_array_equal(base.pairs, got.pairs)
print("SELFJOIN-EXACT")

# --- add(): grow the index; the replica ingests the DELTA slab (no full
# re-place) and still matches the single-device probe over the grown corpus
extra = make_protein_sets(SyntheticProteinConfig(
    n_refs=40, n_homolog_queries=1, n_decoy_queries=1,
    ref_len_mean=90, ref_len_std=12, sub_rates=(0.05,), seed=43))
sh4 = ShardedIndex(idx)            # snapshots the 150-ref partition
nid0, *_ = sh4.topk(q, k=6, cap=64)
idx.add(extra["ref_ids"], extra["ref_lens"])
nid, nd, *_ = sh4.topk(q, k=6, cap=64)      # delta refresh, not a reload
assert sh4._delta is not None, "expected base+delta slabs after add()"
want_id2, want_d2, *_ = topk_probe(idx, q, k=6, cap=64)
np.testing.assert_array_equal(nid, np.asarray(want_id2))
np.testing.assert_array_equal(nd, np.asarray(want_d2))
got = lsh_self_join(idx, n_shards=4)
np.testing.assert_array_equal(lsh_self_join(idx, n_shards=1).pairs,
                              got.pairs)
print("ADD-EXACT")

# --- flip layout under sharding: the expanded table partitions the same
# way (n_bands == 1); ring probe bit-exact for every n_shards
idxf = SignatureIndex.build(cfg, data["ref_ids"], data["ref_lens"],
                            layout="flip")
wf = topk_probe(idxf, q, k=6, cap=64)
for n in (1, 2, 4):
    shf = ShardedIndex(idxf, Mesh(np.array(jax.devices()[:n]), ("data",)))
    gf = shf.topk(q, k=6, cap=64)
    np.testing.assert_array_equal(gf[0], np.asarray(wf[0]))
    np.testing.assert_array_equal(gf[1], np.asarray(wf[1]))
    assert (gf[2], gf[3]) == (wf[2], wf[3])
print("FLIP-EXACT")

# --- fresh-Mesh recompile trap: two self-joins through two freshly
# constructed (equal) meshes must reuse ONE cached emission program.
# A uniform-demand corpus (identical rows -> one live bucket, one live
# cap) pins the SPMD shard_map path; skewed corpora take the ragged
# per-shard path, which never builds a mesh program at all.
from repro.allpairs.selfjoin import _emit_sharded_cached, _emit_sharded_fn
uni = SignatureIndex.build(cfg, np.repeat(data["ref_ids"][:1], 24, axis=0),
                           np.repeat(data["ref_lens"][:1], 24))
_emit_sharded_cached.cache_clear()
m1 = Mesh(np.array(jax.devices()[:4]), ("data",))
j1 = lsh_self_join(uni, n_shards=4, mesh=m1)
info = _emit_sharded_cached.cache_info()
assert info.currsize == 1, info         # the SPMD path actually ran
m2 = Mesh(np.array(jax.devices()[:4]), ("data",))
j2 = lsh_self_join(uni, n_shards=4, mesh=m2)
info = _emit_sharded_cached.cache_info()
assert info.currsize == 1 and info.hits >= 1, info
np.testing.assert_array_equal(j1.pairs, j2.pairs)
assert _emit_sharded_fn(m1, "data", 32) is _emit_sharded_fn(
    Mesh(np.array(jax.devices()[:4]), ("data",)), "data", 32)
print("CACHE-STABLE")

# --- save -> load round-trip of a sharded index, served sharded
import tempfile, os
path = os.path.join(tempfile.mkdtemp(), "sharded.npz")
idx4 = SignatureIndex.build(cfg, data["ref_ids"], data["ref_lens"],
                            n_shards=4)
idx4.save(path)
loaded = SignatureIndex.load(path, expected_cfg=cfg)
assert loaded.n_shards == 4
shl = ShardedIndex(loaded)
nid, nd, *_ = shl.topk(q, k=6, cap=32)
np.testing.assert_array_equal(nid, want_id)
np.testing.assert_array_equal(nd, want_d)
print("ROUNDTRIP-EXACT")

# --- multi-device waves bit-exact vs single device
rng = np.random.default_rng(2)
ids, lens = data["ref_ids"], data["ref_lens"]
pairs = np.stack([rng.integers(0, 150, 48), rng.integers(0, 150, 48)],
                 axis=1).astype(np.int32)
s1 = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8))
s4 = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8, n_devices=4))
np.testing.assert_array_equal(s1.scores, s4.scores)
p1 = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8, prefilter=True))
p4 = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8, prefilter=True,
                                              n_devices=4))
np.testing.assert_array_equal(p1.scores, p4.scores)
np.testing.assert_array_equal(p1.kept, p4.kept)
print("WAVES-EXACT")
"""


@pytest.mark.slow
def test_sharded_paths_forced_four_devices():
    """The real multi-device programs (shard_map emission, ppermute probe
    ring, SPMD-split waves) under XLA_FLAGS-forced 4 host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    for marker in ("PROBE-EXACT", "SELFJOIN-EXACT", "ADD-EXACT",
                   "FLIP-EXACT", "CACHE-STABLE",
                   "ROUNDTRIP-EXACT", "WAVES-EXACT"):
        assert marker in out.stdout, (marker, out.stdout, out.stderr)
