"""The wavefront kernel's PID mode (interpret mode on the CPU) against the
host definition: the row wave's DP matrix walked back by
``_traceback_pid``. Score, identities and alignment length must agree bit
for bit, including where the walk's argmax and predecessor rules meet
ties, and the all-pairs scheduler's Pallas PID route must give the same
pairs, PIDs and families as its host route."""
import numpy as np
import pytest

from repro.align.smith_waterman import percent_identity, sw_wave_pid
from repro.allpairs import (AllPairsConfig, WaveConfig, all_pairs_search,
                            score_pairs, tiles)
from repro.core import LSHConfig
from repro.core.alphabet import PAD, encode
from repro.data import FamilyCorpusConfig, make_family_corpus
from repro.kernels import ops


def _block(seqs, Lq=None, Lr=None):
    """[(q, r)] int8 pairs -> PAD-padded (B, Lq) x (B, Lr) blocks."""
    Lq = Lq or max(1, max(len(q) for q, _ in seqs))
    Lr = Lr or max(1, max(len(r) for _, r in seqs))
    qs = np.full((len(seqs), Lq), PAD, np.int8)
    rs = np.full((len(seqs), Lr), PAD, np.int8)
    for b, (q, r) in enumerate(seqs):
        qs[b, :len(q)] = q
        rs[b, :len(r)] = r
    return qs, rs


def _mutate(rng, s, rate, indel=0.0):
    out = []
    for a in s.tolist():
        u = rng.random()
        if u < indel / 2:
            continue                                 # deletion
        if u < indel:
            out.append(int(rng.integers(0, 20)))     # insertion
        out.append(int(rng.integers(0, 20)) if rng.random() < rate else a)
    return np.array(out, np.int8)


def _case(name):
    rng = np.random.default_rng(sum(map(ord, name)))
    rand = lambda n: rng.integers(0, 20, n).astype(np.int8)  # noqa: E731
    if name == "random":
        return _block([(rand(rng.integers(1, 97)), rand(rng.integers(1, 161)))
                       for _ in range(16)])
    if name == "homologs":
        pairs = []
        for _ in range(12):
            s = rand(rng.integers(40, 120))
            pairs.append((s, _mutate(rng, s, 0.15, indel=0.05)))
        return _block(pairs)
    if name == "ties":          # poly-W and repeats: argmax and walk ties
        w = encode("W")[0]
        ilmv = encode("ILMV")
        return _block([
            (np.full(40, w, np.int8), np.full(60, w, np.int8)),
            (encode("ACDACDACDACD"), encode("ACDACDACDACDACDACDACD")),
            (encode("WWAWWAWWA"), encode("AWWAWWAWWAWW")),
            (encode("KKKKRRRRKKKK"), encode("RRRRKKKKRRRRKKKK")),
            (encode("IVIVIVLLL"), encode("VIVIVILLLIV")),
            (encode("GAGAGAGAGA"), encode("AGAGAGAGAG")),
            (encode("MMMMMMMM"), encode("MMMMLMMMMM")),
            (encode("HW"), encode("WH"))]
            # short pairs over I, L, M, V: equal scores by different walks
            + [(ilmv[rng.integers(0, 4, rng.integers(3, 13))],
                ilmv[rng.integers(0, 4, rng.integers(3, 13))])
               for _ in range(40)])
    if name == "zero_and_pad":  # zero-score pairs and all-PAD wave lanes
        qs, rs = _block([(encode("WWWW"), encode("GGGGG")),
                         (encode("A"), encode("C")),
                         (encode("IIIIII"), encode("VVVVVV")),
                         (rand(30), rand(30))], Lq=32, Lr=48)
        qs[3] = PAD
        rs[1] = PAD
        return qs, rs
    if name == "quantum_edges":  # lengths on and beside the 64 quantum
        lens = [(63, 64), (64, 65), (65, 63), (127, 128), (128, 127),
                (1, 128), (128, 1), (64, 64)]
        pairs = []
        for lq, lr in lens:
            s = rand(max(lq, lr))
            pairs.append((s[:lq], _mutate(rng, s, 0.2)[:lr]))
        return _block(pairs, Lq=128, Lr=128)
    raise ValueError(name)


@pytest.mark.parametrize("name", ["random", "homologs", "ties",
                                  "zero_and_pad", "quantum_edges"])
def test_pid_kernel_matches_host_walk(name):
    qs, rs = _case(name)
    got = np.asarray(ops.wavefront_pid(qs, rs, interpret=True))
    assert got.shape == (len(qs), 3) and got.dtype == np.int32
    pid, length, score = sw_wave_pid(qs, rs)
    np.testing.assert_array_equal(got[:, 0], score)
    np.testing.assert_array_equal(got[:, 2], length)
    np.testing.assert_array_equal(100.0 * got[:, 1]
                                  / np.maximum(got[:, 2], 1), pid)
    for b in range(len(qs)):
        q, r = qs[b][qs[b] != PAD], rs[b][rs[b] != PAD]
        if len(q) and len(r):
            want = percent_identity(q, r)
            assert (pid[b], length[b], score[b]) == want
        else:
            assert tuple(got[b]) == (0, 0, 0)


def test_pid_kernel_counts_equal_residues_not_positive_scores():
    """I/V and F/Y score 3 in BLOSUM62: a run of them aligns with a
    positive score and no identity."""
    qs, rs = _block([(encode("IIIIFFFF"), encode("VVVVYYYY")),
                     (encode("IIIIFFFF"), encode("IIIIYYYY"))])
    got = np.asarray(ops.wavefront_pid(qs, rs, interpret=True))
    assert got[0, 0] > 0 and got[0, 1] == 0 and got[0, 2] == 8
    assert got[1, 1] == 4 and got[1, 2] == 8


@pytest.fixture(scope="module")
def corpus():
    return make_family_corpus(FamilyCorpusConfig(
        n_families=10, family_size=3, n_singletons=30, len_mean=90,
        len_std=12, sub_rate=0.04, seed=5))


@pytest.mark.parametrize("wave", [
    WaveConfig(wave_batch=16, with_pid=True),
    # a finer ladder: many shapes, each wave's lengths ragged under it
    WaveConfig(wave_batch=16, with_pid=True, len_quantum=32),
    WaveConfig(wave_batch=16, with_pid=True, prefilter=True,
               prefilter_min=40)], ids=["device", "ragged", "prefilter"])
def test_score_pairs_pallas_pid_matches_host_route(corpus, monkeypatch,
                                                   wave):
    ids, lens = corpus["ids"], corpus["lens"]
    rng = np.random.default_rng(3)
    n = len(lens)
    pairs = np.stack([rng.integers(0, n, 40), rng.integers(0, n, 40)],
                     axis=1).astype(np.int32)
    host = score_pairs(ids, lens, pairs, wave)
    monkeypatch.setattr(tiles, "on_tpu", lambda: True)
    kern = score_pairs(ids, lens, pairs, wave)
    np.testing.assert_array_equal(kern.scores, host.scores)
    np.testing.assert_array_equal(kern.pid, host.pid)
    np.testing.assert_array_equal(kern.aln_len, host.aln_len)
    assert (kern.n_waves, kern.n_shapes) == (host.n_waves, host.n_shapes)


def test_all_pairs_search_pid_routes_agree(corpus, monkeypatch):
    ids, lens = corpus["ids"], corpus["lens"]
    cfg = AllPairsConfig(lsh=LSHConfig(k=3, T=13, f=32, d=1),
                         wave=WaveConfig(with_pid=True))
    host = all_pairs_search(ids, lens, cfg)
    monkeypatch.setattr(tiles, "on_tpu", lambda: True)
    kern = all_pairs_search(ids, lens, cfg)
    assert len(host.pairs) > 0
    np.testing.assert_array_equal(kern.pairs, host.pairs)
    np.testing.assert_array_equal(kern.scored.scores, host.scored.scores)
    np.testing.assert_array_equal(kern.scored.pid, host.scored.pid)
    np.testing.assert_array_equal(kern.scored.aln_len, host.scored.aln_len)
    np.testing.assert_array_equal(kern.labels, host.labels)
    assert host.families.edge_mask.any()
