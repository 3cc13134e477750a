"""Seeded data generators, vectorised copies of the program's
``data/synthetic.py`` (``make_family_corpus``, ``make_protein_sets``).

Two deliberate departures, so that a seed changes the data and not the
work: lengths are the stratified quantiles of the stated normal
distribution (the same multiset of lengths for every seed, in a seeded
order), and residues are drawn through a 65,536-entry table of the
Swiss-Prot composition (frequencies exact to 2^-16).
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

ALPHABET = 20
PAD = 20            # the program's padding id (scores 0 / sentinel)

# Swiss-Prot amino-acid composition in ARNDCQEGHILKMFPSTWYV order (the
# program's AA_FREQ, copied).
AA_FREQ = np.array([
    0.0826, 0.0553, 0.0406, 0.0546, 0.0137, 0.0393, 0.0674, 0.0708,
    0.0227, 0.0593, 0.0966, 0.0582, 0.0241, 0.0386, 0.0474, 0.0660,
    0.0535, 0.0110, 0.0292, 0.0687,
])
AA_FREQ = AA_FREQ / AA_FREQ.sum()

_TABLE = np.searchsorted(np.cumsum(AA_FREQ),
                         (np.arange(1 << 16) + 0.5) / (1 << 16)).astype(np.int8)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed up to 2**63."""
    return np.random.default_rng([int(seed) & ((1 << 63) - 1), stream])


def residues(rng: np.random.Generator, n: int) -> np.ndarray:
    return _TABLE[rng.integers(0, 1 << 16, size=n, dtype=np.uint32)]


def stratified_lengths(rng, n: int, mean: float, std: float,
                       lo: int = 30) -> np.ndarray:
    """The n stratified quantiles of N(mean, std), floored at ``lo``, in a
    seeded order: every seed gets the same multiset of lengths."""
    nd = NormalDist(mean, std)
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = np.maximum(lo, np.rint(q)).astype(np.int32)
    return lens[rng.permutation(n)]


def pad_rows(flat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenated residues + lengths -> (N, max len) int8, PAD-padded."""
    L = int(lens.max()) if len(lens) else 1
    out = np.full((len(lens), L), PAD, np.int8)
    out[np.arange(L)[None, :] < lens[:, None]] = flat
    return out


def mutate(rng, rows: np.ndarray, lens: np.ndarray, rate) -> np.ndarray:
    """Point substitutions at ``rate`` (scalar or per row) inside each
    row's length; the substituted residue follows the composition."""
    rate = np.broadcast_to(np.asarray(rate, np.float64), (len(rows),))
    inside = np.arange(rows.shape[1])[None, :] < lens[:, None]
    hit = (rng.random(rows.shape) < rate[:, None]) & inside
    out = rows.copy()
    out[hit] = residues(rng, int(hit.sum()))
    return out


def family_corpus(seed: int, n: int, len_mean: float, len_std: float,
                  family_size: int, family_share: float,
                  sub_rate: float) -> dict:
    """Planted families (one founder plus ``family_size - 1`` substituted
    copies, covering ``family_share`` of the corpus) and unrelated
    singletons, shuffled. Returns ids (N, L) int8, lens (N,), labels (N,)
    (the planted family; singletons get their own)."""
    rng = rng_for(seed, 1)
    n_fam = int(n * family_share) // family_size
    n_single = n - n_fam * family_size
    f_lens = stratified_lengths(rng, n_fam, len_mean, len_std)
    s_lens = stratified_lengths(rng, n_single, len_mean, len_std)
    founders = pad_rows(residues(rng, int(f_lens.sum())), f_lens)
    copies = np.repeat(founders, family_size - 1, axis=0)
    c_lens = np.repeat(f_lens, family_size - 1)
    copies = mutate(rng, copies, c_lens, sub_rate)
    singles = pad_rows(residues(rng, int(s_lens.sum())), s_lens)
    L = max(founders.shape[1], singles.shape[1])

    def widen(a):
        return np.pad(a, ((0, 0), (0, L - a.shape[1])), constant_values=PAD)

    ids = np.concatenate([widen(founders), widen(copies), widen(singles)])
    lens = np.concatenate([f_lens, c_lens, s_lens]).astype(np.int32)
    labels = np.concatenate([
        np.arange(n_fam), np.repeat(np.arange(n_fam), family_size - 1),
        n_fam + np.arange(n_single)]).astype(np.int32)
    perm = rng.permutation(n)
    return dict(ids=ids[perm], lens=lens[perm], labels=labels[perm])


def protein_sets(seed: int, n_refs: int, len_mean: float, len_std: float,
                 n_queries: int, homolog_share: float,
                 sub_rates: list) -> dict:
    """A reference set and a query pool: ``homolog_share`` of the queries
    are substituted copies of a seeded reference (the rate cycling over
    ``sub_rates``), the rest unrelated decoys of the same length law.
    Returns ref_ids, ref_lens, query_ids, query_lens, parents (-1 for a
    decoy)."""
    rng = rng_for(seed, 2)
    r_lens = stratified_lengths(rng, n_refs, len_mean, len_std)
    r_ids = pad_rows(residues(rng, int(r_lens.sum())), r_lens)
    n_hom = int(round(n_queries * homolog_share))
    parents = rng.integers(0, n_refs, size=n_hom)
    rates = np.resize(np.asarray(sub_rates, np.float64), n_hom)
    h_lens = r_lens[parents]
    h_ids = mutate(rng, r_ids[parents], h_lens, rates)
    d_lens = stratified_lengths(rng, n_queries - n_hom, len_mean, len_std)
    d_ids = pad_rows(residues(rng, int(d_lens.sum())), d_lens)
    L = max(h_ids.shape[1], d_ids.shape[1])
    q_ids = np.full((n_queries, L), PAD, np.int8)
    q_ids[:n_hom, :h_ids.shape[1]] = h_ids
    q_ids[n_hom:, :d_ids.shape[1]] = d_ids
    q_lens = np.concatenate([h_lens, d_lens]).astype(np.int32)
    par = np.concatenate([parents, np.full(n_queries - n_hom, -1)])
    perm = rng.permutation(n_queries)
    return dict(ref_ids=r_ids, ref_lens=r_lens, query_ids=q_ids[perm],
                query_lens=q_lens[perm], parents=par[perm])
