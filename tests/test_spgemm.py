"""SpGEMM candidate generation: ONE masked sparse-product primitive behind
self-join, delta-join, and probe (``repro.index.spgemm``).

The contract under test: bucket slabs are CSRs of a sequence×bucket
incidence matrix A, candidates are masks over the semiring AᵀA, and the
two orchestrations behind ``join_impl=`` — the fused device-resident
SpGEMM path and the legacy host-merge + grow-and-retry path — produce
BIT-IDENTICAL result arrays across shard counts, segment layouts, Hamming
filters, and the flip layout; the probe is a row slice of the same
product; warmed joins never retrace; and the wider-f (64/128) folded band
keys keep the join and probe exact.
"""
import json

import numpy as np
import pytest
import jax.numpy as jnp

from repro.allpairs import (JoinPrefilter, brute_force_collisions,
                            lsh_delta_join, lsh_self_join)
from repro.core import LSHConfig
from repro.core.join import (PACKED_KEY_MAX_ID, band_keys, compact_pairs,
                             dedup_pairs, pack_unique_pairs)
from repro.data import FamilyCorpusConfig, make_family_corpus
from repro.index import SignatureIndex
from repro.index import service as index_service
from repro.index.spgemm import (masked_pair_product, match_buckets,
                                row_product_positions, spgemm_join_self,
                                spgemm_join_self_keys)
from repro.kernels.ref import spgemm_upper_ref
from repro.kernels.spgemm import upper_pairs_kernel
from repro.obs import SENTINEL
from repro.util import next_pow2

CFG = LSHConfig(k=3, T=13, f=32, d=1)


@pytest.fixture(scope="module")
def corpus():
    return make_family_corpus(FamilyCorpusConfig(
        n_families=12, family_size=3, n_singletons=36, len_mean=90,
        len_std=12, sub_rate=0.04, seed=11))


@pytest.fixture(scope="module")
def index(corpus):
    return SignatureIndex.build(CFG, corpus["ids"], corpus["lens"])


# ----------------------------------------------- join_impl equivalence grid
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("d_filter", [None, CFG.d])
def test_join_impl_equivalence_grid(index, n_shards, d_filter):
    """legacy and spgemm orchestrations return bit-identical arrays for
    every (n_shards, d) cell, and the unfiltered set is the brute-force
    collision oracle."""
    legacy = lsh_self_join(index, d=d_filter, n_shards=n_shards,
                           join_impl="legacy")
    fused = lsh_self_join(index, d=d_filter, n_shards=n_shards,
                          join_impl="spgemm")
    np.testing.assert_array_equal(legacy.pairs, fused.pairs)
    np.testing.assert_array_equal(legacy.indptr, fused.indptr)
    assert legacy.n_candidates == fused.n_candidates
    if d_filter is None:
        assert {tuple(p) for p in fused.pairs} == \
            brute_force_collisions(index)


def test_join_impl_flip_layout(corpus):
    """The flip layout (each signature in C(f,<=d) buckets of ONE band)
    exercises the dedup pack — a pair can collide many times within the
    single band, so the keyed dup-free path must gate itself off."""
    idx = SignatureIndex.build(CFG, corpus["ids"], corpus["lens"],
                               layout="flip")
    legacy = lsh_self_join(idx, join_impl="legacy")
    fused = lsh_self_join(idx, join_impl="spgemm")
    np.testing.assert_array_equal(legacy.pairs, fused.pairs)
    assert {tuple(p) for p in fused.pairs} == brute_force_collisions(idx)


def test_join_impl_grow_caps(index):
    """A tiny starting capacity converges identically under both impls
    (legacy grows-and-retries; spgemm sizes the output exactly), and a
    max_grow below true demand raises for both — never a silent cap."""
    full = lsh_self_join(index, max_pairs=1 << 16)
    for impl in ("legacy", "spgemm"):
        small = lsh_self_join(index, max_pairs=2, join_impl=impl)
        np.testing.assert_array_equal(small.pairs, full.pairs)
        with pytest.raises(RuntimeError, match="max_grow"):
            lsh_self_join(index, max_pairs=2, max_grow=2, join_impl=impl)
        # max_grow caps GROWTH, not the count: the unique pair count here
        # (119) exceeds the per-band emission max (69), yet with a roomy
        # max_pairs legacy never grows its buffer and so never raises —
        # spgemm must mirror that exactly
        need = int(index.partition(1).pair_totals.max())
        assert need < len(full.pairs)
        big = lsh_self_join(index, max_pairs=1 << 16, max_grow=need,
                            join_impl=impl)
        np.testing.assert_array_equal(big.pairs, full.pairs)


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_delta_join_impl_equivalence(corpus, n_shards):
    """Multi-segment delta join: per-shard cross emission under the bucket
    partition is bit-exact vs the from-scratch join, for both impls."""
    ids, lens = corpus["ids"], corpus["lens"]
    n = len(lens)
    base = n - 24
    idx = SignatureIndex.build(CFG, ids[:base], lens[:base])
    old = lsh_self_join(idx)
    for a, b in ((base, n - 12), (n - 12, n)):      # two sealed segments
        idx.add(ids[a:b], lens[a:b])
    deltas = [lsh_delta_join(idx, base_size=base, n_shards=n_shards,
                             join_impl=impl)
              for impl in ("legacy", "spgemm")]
    np.testing.assert_array_equal(deltas[0].pairs, deltas[1].pairs)
    full = lsh_self_join(SignatureIndex.build(CFG, ids, lens))
    union = np.concatenate([old.pairs, deltas[1].pairs], axis=0)
    union = union[np.lexsort((union[:, 1], union[:, 0]))]
    np.testing.assert_array_equal(union, full.pairs)


def test_prefilter_fused_identical_across_impls(corpus, index):
    pf = JoinPrefilter(ids=corpus["ids"], lens=corpus["lens"],
                       min_score=20)
    legacy = lsh_self_join(index, prefilter=pf, join_impl="legacy")
    fused = lsh_self_join(index, prefilter=pf, join_impl="spgemm")
    np.testing.assert_array_equal(legacy.pairs, fused.pairs)
    np.testing.assert_array_equal(legacy.ungapped, fused.ungapped)
    assert legacy.n_prefiltered == fused.n_prefiltered


# ------------------------------------------------- probe = row slice of AᵀA
def test_probe_is_row_slice_of_product(index):
    """The serving probe resolves to the same structural key match as the
    join: each query row's product window is exactly the matched bucket's
    member list."""
    assert index_service._probe_csr_positions is row_product_positions
    index._ensure_built()
    part = index.partition(1)
    qk = np.asarray(index.query_keys(jnp.asarray(index.sigs)))   # (nb, N)
    for band, (keys_s, offs_s, ids_s) in enumerate(zip(*[
            np.asarray(a) for a in part.probe_arrays(0)])):
        pos, ok, size = row_product_positions(
            jnp.asarray(qk[band]), jnp.asarray(keys_s),
            jnp.asarray(offs_s), cap=8, E=ids_s.shape[0])
        pos, ok, size = map(np.asarray, (pos, ok, size))
        start, end = map(np.asarray, match_buckets(
            jnp.asarray(qk[band]), jnp.asarray(keys_s),
            jnp.asarray(offs_s)))
        for q in range(qk.shape[1]):
            want = set(ids_s[start[q]:end[q]].tolist())
            got = set(ids_s[pos[q][ok[q]]].tolist())
            assert size[q] == len(want)
            if size[q] <= 8:
                assert got == want
                if index.valid[q]:
                    assert q in want          # every row collides with itself


# --------------------------------------------------- fused program variants
def test_keyed_join_matches_dedup_join(index):
    """The dup-free keyed program and the sort-dedup program are
    interchangeable: identical pairs and count off the same slabs."""
    index._ensure_built()
    part = index.partition(1)
    _, offs_s, ids_s = part.device_slabs()
    offs_f = offs_s.reshape(-1, offs_s.shape[-1])
    ids_f = ids_s.reshape(-1, ids_s.shape[-1])
    cap = next_pow2(int(part.pair_totals.max()))
    out_cap = next_pow2(int(part.pair_totals.sum()))
    band_f = jnp.tile(jnp.arange(offs_s.shape[1], dtype=jnp.int32),
                      offs_s.shape[0])
    for d in (None, CFG.d):
        p1, c1 = spgemm_join_self(offs_f, ids_f, index.device_sigs,
                                  cap=cap, out_cap=out_cap, d=d)
        p2, c2 = spgemm_join_self_keys(
            offs_f, ids_f, band_f, index.device_band_keys,
            index.device_sigs, cap=cap, out_cap=out_cap, d=d)
        assert int(c1) == int(c2)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_pack_unique_pairs_wide_id_fallback():
    """Ids past PACKED_KEY_MAX_ID fall back to the multi-key sort +
    scatter pack — same buffer contract, same output."""
    rng = np.random.default_rng(3)
    cand = rng.integers(0, 50, size=(256, 2), dtype=np.int32)
    cand.sort(axis=1)
    cand[rng.random(256) < 0.3] = -1
    packed, n1 = pack_unique_pairs(jnp.asarray(cand), out_cap=128,
                                   id_bound=50)
    wide, n2 = pack_unique_pairs(jnp.asarray(cand), out_cap=128,
                                 id_bound=PACKED_KEY_MAX_ID + 1)
    assert int(n1) == int(n2)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(wide))
    # and both match the primitive dedup+compact composition
    cs, keep = dedup_pairs(jnp.asarray(cand))
    ref, n3 = compact_pairs((cs[:, 0], cs[:, 1]), keep, 128)
    assert int(n1) == int(n3)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(ref))


# ------------------------------------------------------ Pallas kernel parity
def test_upper_kernel_matches_ref_and_product():
    """The Pallas upper-mask kernel (interpret mode on CPU), the vmapped
    jnp product, and the host-loop oracle agree on randomized multi-band
    slabs with pow2 padding."""
    rng = np.random.default_rng(7)
    for trial in range(4):
        nb, U, E = 3, 8, 32
        offs, ids = [], []
        for _ in range(nb):
            cuts = np.sort(rng.integers(0, E, U - 1))
            o = np.concatenate([[0], cuts, [E]]).astype(np.int32)
            offs.append(o)
            ids.append(rng.permutation(E).astype(np.int32))
        offs_s = jnp.asarray(np.stack(offs))
        ids_s = jnp.asarray(np.stack(ids))
        need = max(int(((np.diff(o) * (np.diff(o) - 1)) // 2).sum())
                   for o in offs)
        cap = next_pow2(max(need, 8))
        kern = np.asarray(upper_pairs_kernel(offs_s, ids_s, cap=cap,
                                             slot_block=8, interpret=True))
        prod = np.asarray(jnp.stack([
            masked_pair_product(offs_s[b], ids_s[b], cap=cap)
            for b in range(nb)]))
        np.testing.assert_array_equal(kern, prod)
        for b in range(nb):
            ref = spgemm_upper_ref(np.asarray(offs_s[b]),
                                   np.asarray(ids_s[b]), cap)
            np.testing.assert_array_equal(prod[b], ref)


@pytest.mark.parametrize("tpu,U1,E,cap,route", [
    (True, 1662, 4146, 8192, "pallas"),       # flip d=0, 4,146-seq corpus
    (True, 22, 1331, 1 << 17, "pallas"),      # one shard of a 4-shard join
    (True, 37022, 136818, 1 << 20, "jnp"),    # flip d=1: past the budget
    (True, 16, 1 << 16, 1 << 16, "jnp"),      # the (SB, E) one-hot alone
    (False, 1662, 4146, 8192, "jnp"),         # off TPU: the jnp product
])
def test_emission_route_budget(monkeypatch, tpu, U1, E, cap, route):
    """Emission takes the Pallas kernel only on TPU and only for slabs
    whose quadratic working set fits ``EMIT_KERNEL_MAX_CELLS``."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    assert ops.emission_route(U1, E, cap) == route


# ------------------------------------------------------- recompile sentinel
def test_spgemm_steady_state_no_recompiles(index):
    """Warmed joins retrace nothing: the fused keyed program, the dedup
    pack, and the legacy orchestration all hit their jit caches on every
    subsequent call."""
    for _ in range(2):                                 # warm every program
        for impl in ("legacy", "spgemm"):
            for ns in (1, 2):
                lsh_self_join(index, n_shards=ns, join_impl=impl)
    for site in ("spgemm_join_keys", "spgemm_self", "spgemm_pack"):
        assert SENTINEL.total(site) >= 1, f"site {site} never traced"
    with SENTINEL.expect_no_compiles(message="warmed self-join retraced"):
        for impl in ("legacy", "spgemm"):
            for ns in (1, 2):
                lsh_self_join(index, n_shards=ns, join_impl=impl)


# ------------------------------------------------------------ wider-f (64+)
@pytest.mark.parametrize("f", [64, 128])
def test_wider_f_join_and_probe_exact(corpus, f):
    """f=64/128 signatures fold each band's words through the mix32 chain:
    bucket co-membership is preserved, so the join still equals the
    brute-force oracle and every valid row probes itself."""
    cfg = LSHConfig(k=3, T=13, f=f, d=3, scheme="splitmix")
    idx = SignatureIndex.build(cfg, corpus["ids"], corpus["lens"])
    join = lsh_self_join(idx)
    assert {tuple(p) for p in join.pairs} == brute_force_collisions(idx)
    # exact multiword Hamming filter stays a subset with exact membership
    filt = lsh_self_join(idx, d=cfg.d)
    got = {tuple(p) for p in filt.pairs}
    sigs = idx.sigs
    for i, j in join.pairs:
        dist = sum(bin(int(a ^ b)).count("1")
                   for a, b in zip(sigs[i], sigs[j]))
        assert ((int(i), int(j)) in got) == (dist <= cfg.d)
    # probe self-hit through the same folded keys
    cand, sizes = idx.probe(jnp.asarray(idx.sigs), cap=64)
    cand = np.asarray(cand)
    for q in range(idx.size):
        if idx.valid[q]:
            assert q in cand[q]


def test_wider_f_band_keys_fold_exact(corpus):
    """Folded keys collide exactly when the band bits are equal (the
    ~2^-32 accidental-collision tail can only ADD candidates)."""
    cfg = LSHConfig(k=3, T=13, f=64, d=3, scheme="splitmix")
    idx = SignatureIndex.build(cfg, corpus["ids"], corpus["lens"])
    from repro.core.simhash import unpack_bits
    from repro.core.join import band_bit_groups
    keys = np.asarray(band_keys(jnp.asarray(idx.sigs), 64, idx.bands,
                                interleave=idx.interleave,
                                key_hash=idx.key_hash))
    bits = np.asarray(unpack_bits(jnp.asarray(idx.sigs), 64))
    groups = band_bit_groups(64, idx.bands, interleave=idx.interleave)
    n = idx.size
    for b, grp in enumerate(groups):
        for i in range(0, n, 7):
            for j in range(i + 1, n, 13):
                if (bits[i, grp] == bits[j, grp]).all():
                    assert keys[i, b] == keys[j, b]


def test_wider_f_fingerprint_and_roundtrip(corpus, tmp_path):
    cfg64 = LSHConfig(k=3, T=13, f=64, d=3, scheme="splitmix")
    idx = SignatureIndex.build(cfg64, corpus["ids"], corpus["lens"])
    idx32 = SignatureIndex.build(
        LSHConfig(k=3, T=13, f=32, d=1, scheme="splitmix"),
        corpus["ids"], corpus["lens"])
    assert idx.fingerprint != idx32.fingerprint
    d = tmp_path / "f64"
    idx.save(d)
    re = SignatureIndex.load(d, expected_cfg=cfg64)
    a = lsh_self_join(idx)
    b = lsh_self_join(re)
    np.testing.assert_array_equal(a.pairs, b.pairs)


def test_java_scheme_rejects_wide_f():
    with pytest.raises(AssertionError, match="32 bits"):
        LSHConfig(k=3, T=13, f=64, d=1, scheme="java")


# ----------------------------------------------------- metrics CLI carrier
def test_allpairs_cli_metrics_out_and_merge(tmp_path):
    """--metrics-out writes a mergeable registry snapshot; --metrics-merge
    folds a worker snapshot in before rendering (the cross-process
    histogram aggregation satellite, end to end through the CLI)."""
    from repro.launch.allpairs import main as allpairs_main
    from repro.obs import Registry, registry_state

    worker = Registry()
    worker.counter("worker_pairs_total", "pairs from a worker shard")\
        .labels().inc(41)
    h = worker.histogram("worker_join_ms", "worker join latency",
                         bounds=(1.0, 10.0, 100.0))
    h.labels().observe(3.0)
    h.labels().observe(30.0)
    wpath = tmp_path / "worker_metrics.json"
    wpath.write_text(json.dumps(registry_state(worker)))

    mpath = tmp_path / "metrics.json"
    allpairs_main(["--n-families", "4", "--family-size", "3",
                   "--n-singletons", "8", "--len-mean", "60",
                   "--min-pid", "30",
                   "--metrics-out", str(mpath),
                   "--metrics-merge", str(wpath)])
    merged = json.loads(mpath.read_text())["families"]
    assert merged["worker_pairs_total"]["children"][0][1] == 41
    hist = merged["worker_join_ms"]["children"][0][1]
    assert hist["counts"] == [1, 1, 0, 1] or sum(hist["counts"]) == 2
