"""Wavefront (anti-diagonal) Smith-Waterman: linear-gap bit-exactness vs
the row wave, affine (Gotoh) bit-exactness vs the numpy oracle, the int16
lane guard boundary, Pallas-kernel parity under interpret mode, routing
validation, recompile-sentinel steadiness across rung x quantum, and the
prefilter-fused self-join (survivors bit-exact with post-hoc filtering)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.align import gotoh
from repro.align.smith_waterman import (GAP, dp_scores_block,
                                        sw_align_batch, sw_gather_scores,
                                        ungapped_xdrop_scores)
from repro.allpairs import (AllPairsConfig, JoinPrefilter, WaveConfig,
                            all_pairs_search, lsh_self_join, score_pairs)
from repro.core import LSHConfig
from repro.core.alphabet import PAD
from repro.data import FamilyCorpusConfig, make_family_corpus
from repro.index import SignatureIndex
from repro.kernels import ops
from repro.kernels.ref import sw_affine_ref
from repro.obs import SENTINEL

CFG = LSHConfig(k=3, T=13, f=32, d=1)


def _ragged_block(rng, B, Lq, Lr, *, all_pad_rows=(), len1_rows=()):
    """(B, Lq) x (B, Lr) int8 PAD-padded block with ragged true lengths,
    plus forced all-PAD and length-1 rows."""
    qs = np.full((B, Lq), PAD, np.int8)
    rs = np.full((B, Lr), PAD, np.int8)
    for b in range(B):
        if b in all_pad_rows:
            continue
        lq = 1 if b in len1_rows else int(rng.integers(1, Lq + 1))
        lr = 1 if b in len1_rows else int(rng.integers(1, Lr + 1))
        qs[b, :lq] = rng.integers(0, 20, lq, dtype=np.int8)
        rs[b, :lr] = rng.integers(0, 20, lr, dtype=np.int8)
    return qs, rs


@pytest.fixture(scope="module")
def block():
    rng = np.random.default_rng(7)
    return _ragged_block(rng, 24, 96, 80, all_pad_rows=(0, 17),
                         len1_rows=(1, 9))


# --------------------------------------------------------------- linear
def test_wave_linear_matches_rowwave(block):
    """Diagonal sweep == row wave, bit-exact, on ragged blocks including
    all-PAD and length-1 rows."""
    qs, rs = block
    want = sw_align_batch(qs, rs)
    got = np.asarray(gotoh.sw_wave_linear(qs, rs))
    np.testing.assert_array_equal(got, want)


def test_wave_linear_empty_and_singleton():
    qs = np.full((2, 4), PAD, np.int8)
    rs = np.full((2, 4), PAD, np.int8)
    qs[1, 0] = 5
    rs[1, 0] = 5
    got = np.asarray(gotoh.sw_wave_linear(qs, rs))
    np.testing.assert_array_equal(got, sw_align_batch(qs, rs))
    assert got[0] == 0          # all-PAD pair scores exactly 0


def test_wave_linear_odd_diagonal_count():
    """Lq + Lr - 1 not divisible by _DIAG_CHUNK: the padded tail diagonal
    group must be inert."""
    rng = np.random.default_rng(11)
    qs, rs = _ragged_block(rng, 8, 7, 6)
    np.testing.assert_array_equal(np.asarray(gotoh.sw_wave_linear(qs, rs)),
                                  sw_align_batch(qs, rs))


# --------------------------------------------------------------- affine
def test_wave_affine_matches_gotoh_oracle(block):
    qs, rs = block
    got = np.asarray(gotoh.sw_wave_affine(qs, rs))
    for b in range(qs.shape[0]):
        q = qs[b][qs[b] != PAD]
        r = rs[b][rs[b] != PAD]
        want, _ = sw_affine_ref(q, r)
        assert got[b] == want, f"row {b}"


def test_wave_affine_open_eq_extend_degenerates_to_linear(block):
    """open == extend == GAP is bit-exactly the linear recurrence."""
    qs, rs = block
    got = np.asarray(gotoh.sw_wave_affine(qs, rs, gap_open=GAP,
                                          gap_extend=GAP))
    np.testing.assert_array_equal(got, sw_align_batch(qs, rs))


def test_affine_never_exceeds_linear_at_same_open(block):
    """With open=-11 < extend=-1, affine >= the linear-gap score at
    gap=-11 (extensions are cheaper) and <= at gap=-1 (opens are dearer)."""
    qs, rs = block
    aff = np.asarray(gotoh.sw_wave_affine(qs, rs))
    lin_open = np.asarray(gotoh.sw_wave_linear(qs, rs, gap=-11))
    lin_ext = np.asarray(gotoh.sw_wave_linear(qs, rs, gap=-1))
    assert (aff >= lin_open).all()
    assert (aff <= lin_ext).all()


# ---------------------------------------------------------- int16 guard
def test_lane_dtype_boundary():
    """11*L < 2^14 -> int16 lanes; the first length over the bound flips
    to int32 (1489*11 = 16379 < 16384 <= 1490*11)."""
    assert gotoh.lane_dtype(1489, 64) == jnp.int16
    assert gotoh.lane_dtype(1490, 64) == jnp.int32
    assert gotoh.lane_dtype(64, 1490) == jnp.int32
    assert gotoh.lane_dtype(8, 8) == jnp.int16


def test_wave_scores_exact_across_lane_dtype():
    """A perfect long repeat scores linearly in L: pushed past the int16
    guard the int32 lanes must carry the exact score."""
    L = 1490                                   # first int32-lane length
    q = np.tile(np.arange(20, dtype=np.int8), -(-L // 20))[:L]
    qs = q[None, :]
    got = int(np.asarray(gotoh.sw_wave_linear(qs, qs))[0])
    want = int(gotoh._BSENT[q, q].astype(np.int64).sum())
    assert got == want                         # self-alignment, no gaps


# ------------------------------------------------------------- routing
def test_dp_scores_block_routes_and_validates(block):
    qs, rs = block
    lin_row = np.asarray(dp_scores_block(qs, rs, dp_kernel="rowwave"))
    lin_wave = np.asarray(dp_scores_block(qs, rs, dp_kernel="wavefront"))
    np.testing.assert_array_equal(lin_row, lin_wave)
    aff = np.asarray(dp_scores_block(qs, rs, gap_mode="affine"))
    np.testing.assert_array_equal(aff, np.asarray(
        gotoh.sw_wave_affine(qs, rs)))
    with pytest.raises(ValueError, match="wavefront"):
        dp_scores_block(qs, rs, dp_kernel="rowwave", gap_mode="affine")
    with pytest.raises(ValueError, match="dp_kernel"):
        dp_scores_block(qs, rs, dp_kernel="zigzag")
    with pytest.raises(ValueError, match="gap_mode"):
        dp_scores_block(qs, rs, gap_mode="convex")


def test_score_pairs_validates_knobs(block):
    ids = np.asarray(block[0])
    lens = (ids != PAD).sum(axis=1).astype(np.int32)
    pairs = np.array([[0, 1]], np.int32)
    with pytest.raises(ValueError, match="wavefront"):
        score_pairs(ids, lens, pairs, WaveConfig(dp_kernel="rowwave",
                                                 gap_mode="affine"))
    with pytest.raises(ValueError, match="with_pid"):
        score_pairs(ids, lens, pairs, WaveConfig(gap_mode="affine",
                                                 with_pid=True))
    with pytest.raises(ValueError, match="dp_kernel"):
        score_pairs(ids, lens, pairs, WaveConfig(dp_kernel="zigzag"))


# ------------------------------------------------------- Pallas kernel
@pytest.mark.parametrize("B,Lq,Lr", [
    (11, 40, 36),       # one diagonal block
    (10, 96, 80),       # 175 diagonals: the carries cross a block boundary
])
@pytest.mark.parametrize("gap_mode", ["linear", "affine"])
def test_pallas_wavefront_kernel_parity(gap_mode, B, Lq, Lr):
    """The Pallas wavefront kernel (interpret mode off-TPU) is bit-exact
    with the jnp sweep, including a non-multiple-of-bb batch with an
    all-PAD row."""
    rng = np.random.default_rng(3)
    qs, rs = _ragged_block(rng, B, Lq, Lr, all_pad_rows=(4,),
                           len1_rows=(6,))
    got = np.asarray(ops.wavefront_scores(qs, rs, gap_mode=gap_mode))
    want = np.asarray(ops.wavefront_scores(qs, rs, gap_mode=gap_mode,
                                           prefer_ref=True))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- recompile sentinel
def test_warm_wavefront_never_retraces():
    """One gather+wavefront program per (rung, quantum): after warming the
    shape ladder, serving-sized calls never re-trace — across batch rungs,
    length quanta, and both gap modes."""
    rng = np.random.default_rng(5)
    corp = rng.integers(0, 20, (64, 128), dtype=np.int8)
    lens = np.full(64, 128, np.int32)
    ids_dev = jnp.asarray(corp)
    lens_dev = jnp.asarray(lens)

    def call(B, Lq, gap_mode):
        qi = jnp.asarray(rng.integers(0, 64, B, dtype=np.int32))
        ri = jnp.asarray(rng.integers(0, 64, B, dtype=np.int32))
        sw_gather_scores(ids_dev, lens_dev, ids_dev, lens_dev, qi, ri,
                         Lq=Lq, Lr=128, gap_mode=gap_mode
                         ).block_until_ready()

    shapes = [(8, 64), (8, 128), (16, 64), (16, 128)]
    for B, Lq in shapes:            # warm every rung x quantum, both modes
        call(B, Lq, "linear")
        call(B, Lq, "affine")
    with SENTINEL.expect_no_compiles("sw_gather", message="warmed ladder"):
        for B, Lq in shapes * 2:
            call(B, Lq, "linear")
            call(B, Lq, "affine")


# -------------------------------------------------- fused join prefilter
@pytest.fixture(scope="module")
def corpus():
    return make_family_corpus(FamilyCorpusConfig(
        n_families=8, family_size=3, n_singletons=24, len_mean=90,
        len_std=12, sub_rate=0.04, seed=13))


@pytest.fixture(scope="module")
def index(corpus):
    return SignatureIndex.build(CFG, corpus["ids"], corpus["lens"])


def test_fused_prefilter_join_is_postfilter_exact(corpus, index):
    """The in-join prefilter emits exactly the unfused wave prefilter's
    survivors, with identical ungapped scores, and counts the rejects."""
    ids, lens = corpus["ids"], corpus["lens"]
    join = lsh_self_join(index)
    res = score_pairs(ids, lens, join.pairs,
                      WaveConfig(prefilter=True, prefilter_min=40,
                                 with_pid=False))
    fused = lsh_self_join(index, prefilter=JoinPrefilter(
        ids=ids, lens=lens, min_score=40))
    np.testing.assert_array_equal(fused.pairs, join.pairs[res.kept])
    np.testing.assert_array_equal(fused.ungapped, res.ungapped[res.kept])
    assert fused.n_prefiltered == int((~res.kept).sum())
    assert fused.n_candidates == len(fused.pairs)
    # CSR stays valid over the survivor subset
    assert fused.indptr[-1] == len(fused.pairs)
    for i in (0, 5, index.size - 1):
        np.testing.assert_array_equal(
            fused.neighbors(i), fused.pairs[fused.pairs[:, 0] == i, 1])


def test_fused_prefilter_scores_match_direct_ungapped(corpus, index):
    """Survivor scores equal a direct ungapped scan of the kept pairs
    (padding-invariance of the prefilter score)."""
    ids, lens = corpus["ids"], corpus["lens"]
    fused = lsh_self_join(index, prefilter=JoinPrefilter(
        ids=ids, lens=lens, min_score=40))
    L = int(ids.shape[1])
    for (i, j), s in zip(fused.pairs, fused.ungapped):
        direct = int(np.asarray(ungapped_xdrop_scores(
            ids[None, i, :L], ids[None, j, :L]))[0])
        assert direct == s


def test_fused_prefilter_min_score_validation(corpus, index):
    with pytest.raises(ValueError, match="min_score"):
        lsh_self_join(index, prefilter=JoinPrefilter(
            ids=corpus["ids"], lens=corpus["lens"], min_score=0))


def test_all_pairs_search_fused_equals_unfused(corpus):
    """End to end: fuse_prefilter=True produces the same families and the
    same surviving edges as the unfused prefilter pipeline."""
    wave = WaveConfig(with_pid=False, prefilter=True, prefilter_min=40)
    base = AllPairsConfig(wave=wave)
    fused_cfg = AllPairsConfig(wave=wave, fuse_prefilter=True)
    a = all_pairs_search(corpus["ids"], corpus["lens"], base)
    b = all_pairs_search(corpus["ids"], corpus["lens"], fused_cfg)
    np.testing.assert_array_equal(b.pairs, a.pairs[a.scored.kept])
    np.testing.assert_array_equal(a.labels, b.labels)
    kept_scores = a.scored.scores[a.scored.kept]
    np.testing.assert_array_equal(b.scored.scores, kept_scores)


@pytest.mark.parametrize("gap_mode", ["linear", "affine"])
def test_family_labels_stable_across_gap_modes(corpus, gap_mode):
    """Calibrated thresholds give the same families under both gap modes
    (family alignments in the benchmark corpus are gapless, where Gotoh
    and linear scoring coincide)."""
    cfg = AllPairsConfig(wave=WaveConfig(with_pid=False, gap_mode=gap_mode),
                         min_score=150)
    res = all_pairs_search(corpus["ids"], corpus["lens"], cfg)
    want = all_pairs_search(
        corpus["ids"], corpus["lens"],
        AllPairsConfig(wave=WaveConfig(with_pid=False), min_score=150))
    np.testing.assert_array_equal(res.labels, want.labels)
