"""repro.allpairs: self-join exactness, tiled SW waves (device-resident
gather, ungapped X-drop prefilter, async drain ring), clustering, and the
batched Smith-Waterman edge cases (empty sets, length-1, all-PAD, PID
parity between the wave and the per-pair path)."""
import numpy as np
import pytest

from repro.align.smith_waterman import (GAP, percent_identity,
                                        sw_align_batch, sw_score,
                                        sw_wave_pid, ungapped_xdrop_scores)
from repro.allpairs import (AllPairsConfig, WaveConfig, all_pairs_search,
                            brute_force_collisions, cluster_families,
                            lsh_self_join, score_pairs, union_find)
from repro.allpairs import tiles
from repro.allpairs.tiles import _iter_wave_chunks
from repro.core import LSHConfig
from repro.core.alphabet import PAD
from repro.data import FamilyCorpusConfig, make_family_corpus
from repro.index import SignatureIndex
from repro.kernels.ref import sw_affine_ref, ungapped_xdrop_ref

CFG = LSHConfig(k=3, T=13, f=32, d=1)


@pytest.fixture(scope="module")
def corpus():
    return make_family_corpus(FamilyCorpusConfig(
        n_families=10, family_size=3, n_singletons=30, len_mean=90,
        len_std=12, sub_rate=0.04, seed=5))


@pytest.fixture(scope="module")
def index(corpus):
    return SignatureIndex.build(CFG, corpus["ids"], corpus["lens"])


# ---------------------------------------------------------------- self-join
def test_selfjoin_matches_bruteforce_collisions(index):
    join = lsh_self_join(index)
    got = {tuple(p) for p in join.pairs}
    assert got == brute_force_collisions(index)
    # upper-triangular, deduplicated, lex-sorted
    assert (join.pairs[:, 0] < join.pairs[:, 1]).all()
    assert len(got) == join.n_candidates == len(join.pairs)
    order = np.lexsort((join.pairs[:, 1], join.pairs[:, 0]))
    np.testing.assert_array_equal(order, np.arange(len(order)))


def test_selfjoin_grow_and_retry_exact(index):
    """A tiny initial capacity must still converge to the exact pair set."""
    small = lsh_self_join(index, max_pairs=2)
    full = lsh_self_join(index, max_pairs=1 << 16)
    np.testing.assert_array_equal(small.pairs, full.pairs)


def test_selfjoin_max_grow_raises(index):
    with pytest.raises(RuntimeError, match="max_grow"):
        lsh_self_join(index, max_pairs=2, max_grow=2)


def test_selfjoin_hamming_filter_subset(index):
    raw = lsh_self_join(index)
    filt = lsh_self_join(index, d=CFG.d)
    got = {tuple(p) for p in filt.pairs}
    assert got <= {tuple(p) for p in raw.pairs}
    # filter keeps exactly the within-d collisions
    sigs = index.sigs
    for i, j in raw.pairs:
        dist = bin(int(sigs[i, 0] ^ sigs[j, 0])).count("1")
        assert ((i, j) in got) == (dist <= CFG.d)


def test_selfjoin_csr_adjacency(index):
    join = lsh_self_join(index)
    assert join.indptr.shape == (index.size + 1,)
    assert join.indptr[-1] == join.n_candidates
    want = {tuple(p) for p in join.pairs}
    got = {(i, int(j)) for i in range(index.size)
           for j in join.neighbors(i)}
    assert got == want


def test_selfjoin_empty_corpus():
    ids = np.zeros((0, 1), np.int8)
    lens = np.zeros((0,), np.int32)
    idx = SignatureIndex.build(CFG, ids, lens)
    join = lsh_self_join(idx)
    assert join.n_candidates == 0 and join.indptr.shape == (1,)


# ---------------------------------------------------------------- SW waves
def test_wave_scores_match_per_pair(corpus):
    """Batched wave == per-pair scores over a randomized pair set."""
    rng = np.random.default_rng(0)
    ids, lens = corpus["ids"], corpus["lens"]
    n = len(lens)
    pairs = np.stack([rng.integers(0, n, 24), rng.integers(0, n, 24)],
                     axis=1).astype(np.int32)
    scored = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8))
    for row, (i, j) in enumerate(pairs):
        assert scored.scores[row] == sw_score(ids[i][:lens[i]],
                                              ids[j][:lens[j]])


def test_wave_pid_matches_per_pair(corpus):
    """PID parity: the batched wave + traceback must be bit-exact with the
    per-pair percent_identity path on a randomized corpus."""
    rng = np.random.default_rng(1)
    ids, lens = corpus["ids"], corpus["lens"]
    n = len(lens)
    pairs = np.stack([rng.integers(0, n, 16), rng.integers(0, n, 16)],
                     axis=1).astype(np.int32)
    scored = score_pairs(ids, lens, pairs,
                         WaveConfig(wave_batch=8, with_pid=True))
    for row, (i, j) in enumerate(pairs):
        pid, length, score = percent_identity(ids[i][:lens[i]],
                                              ids[j][:lens[j]])
        assert scored.pid[row] == pid
        assert scored.aln_len[row] == length
        assert scored.scores[row] == score


def test_wave_empty_candidate_set(corpus):
    scored = score_pairs(corpus["ids"], corpus["lens"],
                         np.zeros((0, 2), np.int32), WaveConfig())
    assert scored.scores.shape == (0,) and scored.n_waves == 0


def test_wave_length_one_sequences():
    ids = np.array([[0], [0], [4]], np.int8)      # A, A, C
    lens = np.ones(3, np.int32)
    pairs = np.array([[0, 1], [0, 2]], np.int32)
    scored = score_pairs(ids, lens, pairs, WaveConfig(with_pid=True))
    assert scored.scores[0] == 4                  # BLOSUM62[A,A]
    assert scored.pid[0] == 100.0 and scored.aln_len[0] == 1
    assert scored.scores[1] == 0                  # A vs C scores 0 locally
    assert scored.pid[1] == 0.0


def test_sw_int16_guard_long_sequences():
    """The gapped wave's int16 carries are guarded at 11*L < 2^14: a pair
    above the guard falls back to int32 and stays bit-exact with the
    (always-int32) matrix path; one below it runs int16 and agrees too."""
    rng = np.random.default_rng(9)
    for L in (180, 1600):       # int16 regime / int32 fallback
        q = rng.integers(0, 20, L).astype(np.int8)
        r = rng.integers(0, 20, L + 16).astype(np.int8)
        _, _, want = percent_identity(q, r)    # int32 DP matrix path
        assert sw_score(q, r) == want
        np.testing.assert_array_equal(
            sw_align_batch(q[None, :], r[None, :]), [want])


def test_wave_all_pad_rows():
    """All-PAD rows (wave padding) score 0 / PID 0 and never poison real
    rows in the same wave."""
    qs = np.full((3, 12), PAD, np.int8)
    rs = np.full((3, 12), PAD, np.int8)
    seq = np.array([12, 3, 4, 16, 5, 0], np.int8)
    qs[1, :6] = seq
    rs[1, :6] = seq
    pid, length, score = sw_wave_pid(qs, rs)
    assert score[0] == score[2] == 0 and pid[0] == 0 and length[0] == 0
    want_pid, want_len, want_score = percent_identity(seq, seq)
    assert (pid[1], length[1], score[1]) == (want_pid, want_len, want_score)
    np.testing.assert_array_equal(
        sw_align_batch(qs, rs), [0, want_score, 0])


def _random_pairs(corpus, m, seed):
    rng = np.random.default_rng(seed)
    n = len(corpus["lens"])
    return np.stack([rng.integers(0, n, m), rng.integers(0, n, m)],
                    axis=1).astype(np.int32)


# ------------------------------------------------- device-resident pipeline
def test_device_vs_host_gather_bitexact_ragged(corpus):
    """Ragged device waves equal the per-pair definitions on the unpadded
    pairs, for score-only, PID, and prefilter waves alike."""
    ids, lens = corpus["ids"], corpus["lens"]
    assert len(set(lens.tolist())) > 1, "corpus must be ragged"
    pairs = _random_pairs(corpus, 32, 3)
    dev = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8,
                                                   with_pid=True))
    devp = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8,
                                                    prefilter=True))
    for row, (i, j) in enumerate(pairs):
        q, r = ids[i, :lens[i]], ids[j, :lens[j]]
        assert (dev.pid[row], dev.aln_len[row], dev.scores[row]) == \
            percent_identity(q, r)
        assert devp.ungapped[row] == ungapped_xdrop_ref(q, r, 1 << 30)
        want = sw_score(q, r) if devp.kept[row] else devp.ungapped[row]
        assert devp.scores[row] == want


def test_max_wave_cells_forces_single_pair_waves(corpus):
    """A cell budget below one padded pair must degrade to B=1 waves and
    still score exactly."""
    ids, lens = corpus["ids"], corpus["lens"]
    pairs = _random_pairs(corpus, 6, 4)
    tiny = WaveConfig(wave_batch=8, max_wave_cells=1)   # << Lq*Lr
    scored = score_pairs(ids, lens, pairs, tiny)
    assert scored.n_waves == len(pairs)                 # B=1 -> one per pair
    ref = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8))
    np.testing.assert_array_equal(scored.scores, ref.scores)


def test_wave_last_chunk_all_padding(corpus):
    """A bucket one pair larger than a wave leaves a last chunk that is
    mostly padding; padding rows must not perturb real scores."""
    ids, lens = corpus["ids"], corpus["lens"]
    # 9 pairs of identical shape with wave_batch 8 -> waves of 8 and 1(+7 pad)
    i = int(np.argmax(lens))
    pairs = np.array([[i, i]] * 9, np.int32)
    scored = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8))
    want = sw_score(ids[i][:lens[i]], ids[i][:lens[i]])
    np.testing.assert_array_equal(scored.scores, [want] * 9)
    assert scored.n_waves == 2


def test_prefilter_survivors_bitexact_rejected_lower_bound(corpus):
    ids, lens = corpus["ids"], corpus["lens"]
    pairs = _random_pairs(corpus, 48, 5)
    full = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=8))
    pre = score_pairs(ids, lens, pairs,
                      WaveConfig(wave_batch=8, prefilter=True,
                                 prefilter_min=40))
    assert pre.kept is not None and pre.ungapped is not None
    # ungapped is a lower bound of SW everywhere
    assert (pre.ungapped <= full.scores).all()
    # survivors re-scored by full SW, bit-exact
    np.testing.assert_array_equal(pre.scores[pre.kept],
                                  full.scores[pre.kept])
    # rejected pairs report the (lower-bound) ungapped score
    np.testing.assert_array_equal(pre.scores[~pre.kept],
                                  pre.ungapped[~pre.kept])
    assert pre.n_prefiltered == int((~pre.kept).sum())


def test_xdrop_recall_on_planted_families(corpus):
    """Prefilter recall: every pair scoring >= the family threshold must
    survive the ungapped X-drop filter (the benchmark's 99% criterion is
    exactly 100% on this corpus), for both x=None and finite x."""
    ids, lens, labels = corpus["ids"], corpus["lens"], corpus["labels"]
    res = lsh_self_join(SignatureIndex.build(CFG, ids, lens))
    full = score_pairs(ids, lens, res.pairs, WaveConfig())
    S = 150                                     # family score threshold
    fam = labels[res.pairs[:, 0]] == labels[res.pairs[:, 1]]
    assert (full.scores[fam] >= S).all(), "planted pairs must score >= S"
    for x in (None, 20):
        pre = score_pairs(ids, lens, res.pairs,
                          WaveConfig(prefilter=True, prefilter_min=40,
                                     xdrop=x))
        high = full.scores >= S
        assert pre.kept[high].all(), f"x={x} lost a high-scoring pair"


def test_prefilter_indel_regime_needs_calibration():
    """Documented limitation: dense indels chop ungapped runs, so the
    gapped/ungapped gap widens and the default threshold loses true pairs —
    the reason the clustering CLI keeps the prefilter opt-in."""
    c = make_family_corpus(FamilyCorpusConfig(
        n_families=8, family_size=3, n_singletons=16, len_mean=150,
        sub_rate=0.02, indel_rate=0.4, seed=3))
    cfg = AllPairsConfig(lsh=LSHConfig(k=3, T=13, f=32, d=4), min_pid=50.0,
                         wave=WaveConfig(with_pid=True, prefilter=True,
                                         prefilter_min=40))
    res = all_pairs_search(c["ids"], c["lens"], cfg)
    full = score_pairs(c["ids"], c["lens"], res.pairs,
                       WaveConfig(with_pid=True))
    true_edge = np.asarray(full.pid) >= 50.0
    # gapped homologs exist whose ungapped lower bound is under-threshold
    assert (res.scored.ungapped[true_edge] < 40).any()
    # and with the prefilter off, none of them are lost
    assert (np.asarray(full.pid)[true_edge] >= 50.0).all()


def test_ungapped_xdrop_monotone_in_x(corpus):
    """Finite X-drop can only terminate runs earlier: score(x) <=
    score(None), and both lower-bound the gapped SW score."""
    ids, lens = corpus["ids"], corpus["lens"]
    pairs = _random_pairs(corpus, 16, 6)
    qm, rm = ids[pairs[:, 0]], ids[pairs[:, 1]]
    inf_sc = np.asarray(ungapped_xdrop_scores(qm, rm, x=None))
    x_sc = np.asarray(ungapped_xdrop_scores(qm, rm, x=10))
    sw = sw_align_batch(qm, rm)
    assert (x_sc <= inf_sc).all()
    assert (inf_sc <= sw).all()


def test_async_ring_depths_agree(corpus):
    """Results are independent of the in-flight ring depth."""
    ids, lens = corpus["ids"], corpus["lens"]
    pairs = _random_pairs(corpus, 24, 7)
    base = score_pairs(ids, lens, pairs, WaveConfig(inflight=0))
    for depth in (1, 2, 8):
        got = score_pairs(ids, lens, pairs, WaveConfig(inflight=depth))
        np.testing.assert_array_equal(got.scores, base.scores)


def test_wave_pallas_kernel_parity(corpus, monkeypatch):
    """The Pallas tile kernel scores == the jnp wave on ragged real pairs."""
    rng = np.random.default_rng(2)
    ids, lens = corpus["ids"], corpus["lens"]
    n = len(lens)
    pairs = np.stack([rng.integers(0, n, 10), rng.integers(0, n, 10)],
                     axis=1).astype(np.int32)
    a = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=4))
    monkeypatch.setattr(tiles, "on_tpu", lambda: True)
    b = score_pairs(ids, lens, pairs, WaveConfig(wave_batch=4))
    np.testing.assert_array_equal(a.scores, b.scores)


@pytest.fixture(scope="module")
def ragged():
    """Twelve sequences of ragged lengths 1..96 and 16 pairs over them,
    self-pairs and a length-1 sequence included."""
    rng = np.random.default_rng(21)
    lens = rng.integers(1, 97, 12).astype(np.int32)
    lens[3] = 1
    ids = np.full((12, 96), PAD, np.int8)
    base = rng.integers(0, 20, 96).astype(np.int8)
    for n, L in enumerate(lens):     # related rows, so scores are not all 0
        s = np.where(rng.random(96) < 0.3, rng.integers(0, 20, 96), base)
        ids[n, :L] = np.roll(s, int(rng.integers(0, 8)))[:L]
    pairs = np.stack([rng.integers(0, 12, 16), rng.integers(0, 12, 16)],
                     axis=1).astype(np.int32)
    pairs[0] = (5, 5)
    pairs[1] = (3, 7)
    return ids, lens, pairs


_KINDS = {
    "ungapped": WaveConfig(wave_batch=8, prefilter=True,
                           prefilter_min=1 << 30, xdrop=20),
    "sw_linear": WaveConfig(wave_batch=8),
    "sw_affine": WaveConfig(wave_batch=8, gap_mode="affine"),
    "pid": WaveConfig(wave_batch=8, with_pid=True),
}


@pytest.mark.parametrize("route", ["kernel", "jnp"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_wave_route_matches_oracle(ragged, monkeypatch, kind, route):
    """Each wave kind on each route — the Pallas kernels (interpreted
    here) and the jnp sweeps — scores every pair slot of a ragged corpus
    as the host oracles of `kernels/ref.py` (and `percent_identity` for
    PID) score the unpadded pair."""
    ids, lens, pairs = ragged
    monkeypatch.setattr(tiles, "on_tpu", lambda: route == "kernel")
    res = score_pairs(ids, lens, pairs, _KINDS[kind])
    for row, (i, j) in enumerate(pairs):
        q, r = ids[i, :lens[i]], ids[j, :lens[j]]
        if kind == "ungapped":
            assert not res.kept[row]
            assert res.scores[row] == ungapped_xdrop_ref(q, r, 20)
        elif kind == "pid":
            assert (res.pid[row], res.aln_len[row], res.scores[row]) == \
                percent_identity(q, r)
        else:
            gaps = (-11, -1) if kind == "sw_affine" else (GAP, GAP)
            assert res.scores[row] == sw_affine_ref(q, r, *gaps)[0]


# ---------------------------------------------------------------- wave plan
def _shape_buckets(pairs, lens, cfg):
    """{(Lq, Lr): pair count} of the padded-length ladder."""
    q = cfg.len_quantum
    lq = np.maximum(q, -(-lens[pairs[:, 0]] // q) * q)
    lr = np.maximum(q, -(-lens[pairs[:, 1]] // q) * q)
    keys, counts = np.unique(np.stack([lq, lr], 1), axis=0,
                             return_counts=True)
    return {(int(a), int(b)): int(m) for (a, b), m in zip(keys, counts)}


def _expected_waves(pairs, lens, cfg, batch, ndev=1):
    """Waves and shapes a plan keyed by padded shape alone makes: each
    bucket of m pairs fills ceil(m / B) waves of the cell-budget batch."""
    buckets = _shape_buckets(pairs, lens, cfg)
    waves = sum(-(-m // (max(1, min(batch, cfg.max_wave_cells // (a * b)))
                         * ndev))
                for (a, b), m in buckets.items())
    return waves, len(buckets)


@pytest.mark.parametrize("wave_batch, ndev", [(64, 1), (256, 1), (64, 4)])
def test_wave_plan_packs_each_shape_across_the_corpus(wave_batch, ndev):
    """Rows far apart (ids more than 1,024 apart in a ~3,000-row corpus)
    share a wave when their padded shape matches: every pair is planned
    once, each (Lq, Lr) bucket fills exactly ceil(m / B) waves, and pairs
    keep their input order within a wave."""
    rng = np.random.default_rng(14)
    n = 3000
    lens = rng.integers(20, 420, n).astype(np.int32)
    i = rng.integers(0, n - 1100, 4000)
    j = i + rng.integers(1025, n - i)
    pairs = np.stack([i, j], 1).astype(np.int32)
    pairs[::2] = pairs[::2, ::-1]           # both orders of the endpoints
    cfg = WaveConfig()
    chunks = list(_iter_wave_chunks(pairs, lens, cfg, wave_batch, ndev))
    seen = np.concatenate([c for c, *_ in chunks])
    np.testing.assert_array_equal(np.sort(seen), np.arange(len(pairs)))
    by_shape: dict = {}
    for chunk, B, Lq, Lr in chunks:
        assert (np.diff(chunk) > 0).all(), "input order within a wave"
        assert 0 < len(chunk) <= B
        by_shape.setdefault((Lq, Lr), []).append((len(chunk), B))
    buckets = _shape_buckets(pairs, lens, cfg)
    assert by_shape.keys() == buckets.keys()
    for shape, waves in by_shape.items():
        m, B = buckets[shape], waves[0][1]
        assert len(waves) == -(-m // B)
        assert all(b == B for _, b in waves)
        assert all(k == B for k, _ in waves[:-1]), "only the last is short"
    assert len(chunks) == _expected_waves(pairs, lens, cfg, wave_batch,
                                          ndev)[0]


def test_score_pairs_wave_count_is_the_shape_plan(corpus):
    """``n_waves`` / ``n_shapes`` of a prefiltered run on a ragged corpus
    are the shape-keyed plan's count: prefilter waves over every pair,
    DP waves over the survivors."""
    ids, lens = corpus["ids"], corpus["lens"]
    pairs = _random_pairs(corpus, 96, 14)
    cfg = WaveConfig(wave_batch=8, prefilter_batch=16, prefilter=True,
                     prefilter_min=40)
    res = score_pairs(ids, lens, pairs, cfg)
    pw, ps = _expected_waves(pairs, lens, cfg, cfg.prefilter_batch)
    dw, ds = _expected_waves(pairs[res.kept], lens, cfg, cfg.wave_batch)
    assert 0 < res.kept.sum() < len(pairs)
    assert (res.n_waves, res.n_shapes) == (pw + dw, ps + ds)


def test_prefilter_waves_across_row_1024_bitexact(corpus):
    """Pairs whose rows lie on both sides of row 1,024 share waves; their
    prefilter and DP scores equal the per-pair references."""
    ids, lens = corpus["ids"], corpus["lens"]
    n, L = ids.shape
    short = np.full((1000, L), PAD, np.int8)
    short[:, :8] = np.arange(8000).reshape(1000, 8) % 20
    big_ids = np.concatenate([ids, short, ids])
    big_lens = np.concatenate([lens, np.full(1000, 8, np.int32), lens])
    far = n + 1000                           # the copy of row 0, past 1,024
    assert far > 1024
    rng = np.random.default_rng(15)
    a = rng.integers(0, n, 40)
    pairs = np.concatenate([
        np.stack([a, far + a], 1),           # a row and its own copy
        np.stack([a, far + rng.integers(0, n, 40)], 1),
        np.stack([far + rng.integers(0, n, 8), rng.integers(0, n, 8)], 1),
        np.stack([rng.integers(0, n, 8), rng.integers(0, n, 8)], 1),
        np.stack([rng.integers(n, far, 8), far + rng.integers(0, n, 8)], 1),
    ]).astype(np.int32)
    res = score_pairs(big_ids, big_lens, pairs,
                      WaveConfig(wave_batch=8, prefilter=True,
                                 prefilter_min=40))
    assert 0 < res.kept.sum() < len(pairs)
    for row, (i, j) in enumerate(pairs):
        q, r = big_ids[i, :big_lens[i]], big_ids[j, :big_lens[j]]
        assert res.ungapped[row] == ungapped_xdrop_ref(q, r, x=1 << 30)
        if res.kept[row]:
            assert res.scores[row] == sw_score(q, r)
        else:
            assert res.scores[row] == res.ungapped[row]


# ---------------------------------------------------------------- clustering
def test_union_find_components():
    edges = np.array([[0, 1], [1, 2], [4, 5]], np.int64)
    labels = union_find(6, edges)
    assert labels[0] == labels[1] == labels[2]
    assert labels[4] == labels[5]
    assert labels[3] not in (labels[0], labels[4])
    # canonical label = smallest member
    assert labels[0] == 0 and labels[4] == 4 and labels[3] == 3


def test_cluster_families_thresholds():
    pairs = np.array([[0, 1], [2, 3], [4, 5]], np.int32)
    pid = np.array([90.0, 30.0, np.nan])
    fams = cluster_families(6, pairs, pid, min_pid=50.0)
    assert fams.n_families == 1
    np.testing.assert_array_equal(fams.families[0], [0, 1])
    np.testing.assert_array_equal(fams.edge_mask, [True, False, False])


def test_all_pairs_search_end_to_end(corpus):
    res = all_pairs_search(corpus["ids"], corpus["lens"],
                           AllPairsConfig(lsh=CFG, min_pid=60.0))
    labels = corpus["labels"]
    # every discovered family must be pure under the planted ground truth
    for fam in res.families.families:
        assert len(set(labels[fam])) == 1, f"mixed family {fam}"
    assert res.families.n_families >= 5       # most planted families surface
    # scored arrays align with the candidate pairs
    assert len(res.scored.scores) == res.join.n_candidates
    assert res.scored.pid is not None


def test_all_pairs_search_reuses_index(corpus, index):
    res = all_pairs_search(corpus["ids"], corpus["lens"],
                           AllPairsConfig(lsh=CFG), index=index)
    assert res.index is index
    with pytest.raises(ValueError, match="corpus"):
        all_pairs_search(corpus["ids"][:4], corpus["lens"][:4],
                         AllPairsConfig(lsh=CFG), index=index)
