"""The generators: the same seed gives the same data, and every seed the
same multiset of lengths (so a seed changes the data, not the work)."""
import numpy as np

from harness import gen


def test_family_corpus_same_seed_same_data():
    kw = dict(n=300, len_mean=120, len_std=30, family_size=4,
              family_share=0.5, sub_rate=0.03)
    a = gen.family_corpus(2**33 + 7, **kw)
    b = gen.family_corpus(2**33 + 7, **kw)
    c = gen.family_corpus(2**33 + 8, **kw)
    for key in ("ids", "lens", "labels"):
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["ids"], c["ids"])
    assert np.array_equal(np.sort(a["lens"]), np.sort(c["lens"]))
    fam = np.bincount(a["labels"])
    assert (fam == 4).sum() == 300 // 2 // 4
    # family members are substituted copies of one founder
    members = np.flatnonzero(a["labels"] == a["labels"][np.argmax(
        fam[a["labels"]])])
    x, y = a["ids"][members[0]], a["ids"][members[1]]
    L = a["lens"][members[0]]
    assert a["lens"][members[1]] == L
    assert 0.85 < np.mean(x[:L] == y[:L]) < 1.0


def test_protein_sets_same_seed_same_data():
    kw = dict(n_refs=500, len_mean=200, len_std=50, n_queries=100,
              homolog_share=0.8, sub_rates=[0.05, 0.15, 0.3])
    a = gen.protein_sets(5, **kw)
    b = gen.protein_sets(5, **kw)
    for key in a:
        assert np.array_equal(a[key], b[key])
    assert (a["parents"] >= 0).sum() == 80
    h = np.flatnonzero(a["parents"] >= 0)[0]
    p = a["parents"][h]
    assert a["query_lens"][h] == a["ref_lens"][p]
    c = gen.protein_sets(6, **kw)
    assert np.array_equal(np.sort(a["ref_lens"]), np.sort(c["ref_lens"]))


def test_residue_composition():
    r = gen.residues(gen.rng_for(1, 0), 200_000)
    freq = np.bincount(r, minlength=20) / len(r)
    assert np.abs(freq - gen.AA_FREQ).max() < 3e-3
