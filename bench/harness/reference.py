"""Plain references for the two operations, written from their published
definitions. Nothing here imports the program or reads what it made.

* Signatures (ScalLoPS, Algorithm 2): every k-shingle of a sequence adds,
  for each k-letter word w with BLOSUM62 score s(shingle, w) >= T, the
  weight s times the +-1 hyperplane row of w (bit j of the word's hash:
  Java ``String.hashCode`` of its letters, or splitmix64 of its base-20
  id). Bit j of the signature is set where the sum is >= 0; a sequence
  with no neighbouring word at all is invalid (paper §5.2).
* Band join: with ``bands`` interleaved bands (bit i in band i % bands) two
  valid sequences are candidates when all bits of some band agree.
* Ungapped prefilter: the best-scoring ungapped segment (X-drop without a
  drop limit): Kadane's maximum along every diagonal.
* Smith-Waterman with a linear gap: the best local alignment score.
* Families: connected components of the pairs scoring >= the threshold.
* Serving: the k nearest valid candidates of a query by (Hamming
  distance, id), -1-padded; an invalid query gets none.

``low=True`` computes the accumulators and DP lanes in bfloat16 instead of
int32: the control that a correct comparison must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

AMINO_ACIDS = "ARNDCQEGHILKMFPSTWYV"
A = 20
# BLOSUM62 (Henikoff & Henikoff 1992) in AMINO_ACIDS order.
BLOSUM62 = np.array([
    [4, -1, -2, -2, 0, -1, -1, 0, -2, -1, -1, -1, -1, -2, -1, 1, 0, -3, -2, 0],
    [-1, 5, 0, -2, -3, 1, 0, -2, 0, -3, -2, 2, -1, -3, -2, -1, -1, -3, -2, -3],
    [-2, 0, 6, 1, -3, 0, 0, 0, 1, -3, -3, 0, -2, -3, -2, 1, 0, -4, -2, -3],
    [-2, -2, 1, 6, -3, 0, 2, -1, -1, -3, -4, -1, -3, -3, -1, 0, -1, -4, -3, -3],
    [0, -3, -3, -3, 9, -3, -4, -3, -3, -1, -1, -3, -1, -2, -3, -1, -1, -2, -2, -1],
    [-1, 1, 0, 0, -3, 5, 2, -2, 0, -3, -2, 1, 0, -3, -1, 0, -1, -2, -1, -2],
    [-1, 0, 0, 2, -4, 2, 5, -2, 0, -3, -3, 1, -2, -3, -1, 0, -1, -3, -2, -2],
    [0, -2, 0, -1, -3, -2, -2, 6, -2, -4, -4, -2, -3, -3, -2, 0, -2, -2, -3, -3],
    [-2, 0, 1, -1, -3, 0, 0, -2, 8, -3, -3, -1, -2, -1, -2, -1, -2, -2, 2, -3],
    [-1, -3, -3, -3, -1, -3, -3, -4, -3, 4, 2, -3, 1, 0, -3, -2, -1, -3, -1, 3],
    [-1, -2, -3, -4, -1, -2, -3, -4, -3, 2, 4, -2, 2, 0, -3, -2, -1, -2, -1, 1],
    [-1, 2, 0, -1, -3, 1, 1, -2, -1, -3, -2, 5, -1, -3, -1, 0, -1, -3, -2, -2],
    [-1, -1, -2, -3, -1, 0, -2, -3, -2, 1, 2, -1, 5, 0, -2, -1, -1, -1, -1, 1],
    [-2, -3, -3, -3, -2, -3, -3, -3, -1, 0, 0, -3, 0, 6, -4, -2, -2, 1, 3, -1],
    [-1, -2, -2, -1, -3, -1, -1, -2, -2, -3, -3, -1, -2, -4, 7, -1, -1, -4, -3, -2],
    [1, -1, 1, 0, -1, 0, 0, 0, -1, -2, -2, 0, -1, -2, -1, 4, 1, -3, -2, -2],
    [0, -1, 0, -1, -1, -1, -1, -2, -2, -1, -1, -1, -1, -2, -1, 1, 5, -2, -2, 0],
    [-3, -3, -4, -4, -2, -2, -3, -2, -2, -3, -2, -3, -1, 1, -4, -3, -2, 11, 2, -3],
    [-2, -2, -2, -3, -2, -1, -2, -3, 2, -1, -1, -2, -1, 3, -3, -2, -2, 2, 7, -2],
    [0, -3, -3, -3, -1, -2, -2, -3, -3, 3, 1, -2, 1, -1, -2, -2, 0, -3, -2, 4],
], np.int32)
NEG = -4096         # score of a padded position: no alignment crosses it


# ------------------------------------------------------------ signatures
def hash_bits(k: int, f: int, scheme: str) -> np.ndarray:
    """(20^k, f) uint8: bit j of each word's hash."""
    W = A ** k
    wid = np.arange(W, dtype=np.uint64)
    if scheme == "java":
        if f > 32:
            raise ValueError("a Java hash has 32 bits")
        h = np.zeros(W, np.uint64)
        for pos in range(k):
            digit = (wid // np.uint64(A ** (k - 1 - pos))) % np.uint64(A)
            ch = np.array([ord(c) for c in AMINO_ACIDS], np.uint64)[digit]
            h = (h * np.uint64(31) + ch) & np.uint64(0xFFFFFFFF)
        words = h[:, None]
        per = 32
    elif scheme == "splitmix":
        n64 = -(-f // 64)
        cols = []
        for r in range(n64):
            z = wid * np.uint64(n64) + np.uint64(r) + np.uint64(0x9E3779B97F4A7C15)
            z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            cols.append(z ^ (z >> np.uint64(31)))
        words = np.stack(cols, axis=1)
        per = 64
    else:
        raise ValueError(f"unknown hash scheme {scheme!r}")
    shifts = np.arange(per, dtype=np.uint64)
    bits = (words[:, :, None] >> shifts) & np.uint64(1)
    return bits.reshape(W, -1)[:, :f].astype(np.uint8)


@functools.lru_cache(maxsize=4)
def word_tables(k: int, T: int, f: int, scheme: str):
    """C (20^k, f) int64: the summed weighted hyperplane rows of each
    word's neighbours; n (20^k,) int64: how many neighbours it has."""
    W = A ** k
    digits = np.stack([(np.arange(W) // A ** (k - 1 - p)) % A
                       for p in range(k)], axis=1)
    signs = hash_bits(k, f, scheme).astype(np.float64) * 2 - 1
    C = np.zeros((W, f), np.int64)
    n = np.zeros(W, np.int64)
    for s in range(0, W, 2048):
        sc = np.zeros((min(2048, W - s), W), np.int64)
        for p in range(k):
            sc += BLOSUM62[digits[s:s + 2048, p]][:, digits[:, p]]
        wt = np.where(sc >= T, sc, 0)
        C[s:s + 2048] = np.rint(wt.astype(np.float64) @ signs).astype(np.int64)
        n[s:s + 2048] = (sc >= T).sum(axis=1)
    return C, n


@functools.partial(jax.jit, static_argnames=("k", "low"))
def _sig_block(ids, lens, C, n, *, k: int, low: bool):
    L = ids.shape[1]
    S = L - k + 1
    pos = jnp.arange(S)
    ok = pos[None, :] + k <= lens[:, None]
    wid = jnp.zeros(ids[:, :S].shape, jnp.int32)
    for p in range(k):
        col = ids[:, p:p + S].astype(jnp.int32)
        ok &= col < A
        wid = wid * A + col
    wid = jnp.where(ok, wid, 0)
    if low:     # a bfloat16 running sum over the shingles
        Cb = C.astype(jnp.bfloat16)

        def step(V, s):
            add = jnp.where(ok[:, s, None], Cb[wid[:, s]], 0)
            return (V + add).astype(jnp.bfloat16), None

        V, _ = jax.lax.scan(step, jnp.zeros((ids.shape[0], C.shape[1]),
                                            jnp.bfloat16), jnp.arange(S))
    else:
        V = jnp.where(ok[..., None], C[wid], 0).sum(axis=1)
    cnt = jnp.where(ok, n[wid], 0).sum(axis=1)
    bits = (V >= 0).astype(jnp.uint32).reshape(ids.shape[0], -1, 32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1)
    return words.astype(jnp.uint32), cnt > 0


def signatures(ids, lens, *, k: int, T: int, f: int, scheme: str,
               low: bool = False, block: int = 1024):
    """(N, L) int8 residues -> (sigs (N, f/32) uint32, valid (N,) bool)."""
    C, n = word_tables(k, T, f, scheme)
    Cd = jnp.asarray(C, jnp.int32)
    nd = jnp.asarray(n, jnp.int32)
    N = len(lens)
    sigs, valid = [], []
    for s in range(0, N, block):
        a = np.asarray(ids[s:s + block])
        b = np.asarray(lens[s:s + block], np.int32)
        m = len(b)
        if m < block and N > block:     # one compiled block shape
            a = np.pad(a, ((0, block - m), (0, 0)), constant_values=A)
            b = np.pad(b, (0, block - m))
        w, v = _sig_block(jnp.asarray(a), jnp.asarray(b), Cd, nd, k=k,
                          low=low)
        sigs.append(np.asarray(w)[:m])
        valid.append(np.asarray(v)[:m])
    return np.concatenate(sigs), np.concatenate(valid)


def band_masks(f: int, bands: int) -> np.ndarray:
    """(bands, f/32) uint32: the bits of each interleaved band."""
    out = np.zeros((bands, f // 32), np.uint32)
    for i in range(f):
        out[i % bands, i // 32] |= np.uint32(1 << (i % 32))
    return out


# ------------------------------------------------------------ all-pairs
def candidate_pairs(sigs, valid, *, f: int, bands: int, d: int):
    """(P, 2) int32 pairs i < j of valid sequences that agree on some band
    and lie within Hamming ``d``, in lexicographic order."""
    masks = band_masks(f, bands)
    idx = np.flatnonzero(valid)
    found = set()
    for m in masks:
        keys = [tuple(r) for r in (sigs[idx] & m)]
        groups: dict = {}
        for i, key in zip(idx.tolist(), keys):
            groups.setdefault(key, []).append(i)
        for members in groups.values():
            for a in range(len(members)):
                for b in range(a + 1, len(members)):
                    found.add((members[a], members[b]))
    if not found:
        return np.zeros((0, 2), np.int32)
    p = np.array(sorted(found), np.int64)
    dist = np.bitwise_count(sigs[p[:, 0]] ^ sigs[p[:, 1]]).sum(axis=1)
    return p[dist <= d].astype(np.int32)


def _sub_rows(q, r, lq, lr, i):
    """Row i of every pair's substitution matrix, NEG outside the pair."""
    S = jnp.asarray(BLOSUM62)
    qi = q[:, i].astype(jnp.int32)
    rr = r.astype(jnp.int32)
    s = S[jnp.minimum(qi, A - 1)[:, None], jnp.minimum(rr, A - 1)]
    inside = (i < lq)[:, None] & (jnp.arange(r.shape[1])[None, :] < lr[:, None])
    return jnp.where(inside, s, NEG)


@functools.partial(jax.jit, static_argnames=("gap", "low"))
def _dp_block(q, r, lq, lr, *, gap: int, low: bool):
    """Best local score per pair, linear gap, one row of H per step; the
    in-row gap chain is a running max (H[j] = max_k h0[k] + gap (j - k))."""
    dt = jnp.bfloat16 if low else jnp.int32
    B, Lr = r.shape
    idx = jnp.arange(Lr, dtype=dt)
    gap_d = jnp.asarray(gap, dt)

    def step(carry, i):
        Hp, best = carry
        s = _sub_rows(q, r, lq, lr, i).astype(dt)
        h0 = jnp.maximum(jnp.maximum(Hp[:, :-1] + s, Hp[:, 1:] + gap_d),
                         jnp.zeros((), dt))
        H = jax.lax.cummax(h0 - gap_d * idx, axis=1) + gap_d * idx
        best = jnp.maximum(best, H.max(axis=1))
        Hp = jnp.concatenate([jnp.zeros((B, 1), dt), H], axis=1)
        return (Hp, best), None

    init = (jnp.zeros((B, Lr + 1), dt), jnp.zeros((B,), dt))
    (_, best), _ = jax.lax.scan(step, init, jnp.arange(q.shape[1]))
    return best.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("low",))
def _ungapped_block(q, r, lq, lr, *, low: bool):
    """Best ungapped segment per pair: Kadane along every diagonal."""
    dt = jnp.bfloat16 if low else jnp.int32
    B, Lr = r.shape

    def step(carry, i):
        Gp, best = carry
        s = _sub_rows(q, r, lq, lr, i).astype(dt)
        G = jnp.maximum(Gp[:, :-1] + s, jnp.zeros((), dt))
        best = jnp.maximum(best, G.max(axis=1))
        return (jnp.concatenate([jnp.zeros((B, 1), dt), G], axis=1),
                best), None

    init = (jnp.zeros((B, Lr + 1), dt), jnp.zeros((B,), dt))
    (_, best), _ = jax.lax.scan(step, init, jnp.arange(q.shape[1]))
    return best.astype(jnp.int32)


def pair_scores(ids, lens, pairs, *, kind: str, gap: int = -4,
                low: bool = False, block: int = 256) -> np.ndarray:
    """Scores of every pair, in blocks of one padded shape."""
    P = len(pairs)
    out = np.zeros(P, np.int32)
    if P == 0:
        return out
    L = -(-int(np.max(lens)) // 128) * 128
    ids = np.asarray(ids)
    if ids.shape[1] < L:
        ids = np.pad(ids, ((0, 0), (0, L - ids.shape[1])), constant_values=A)
    ids = ids[:, :L]
    for s in range(0, P, block):
        pp = pairs[s:s + block]
        m = len(pp)
        pp = np.pad(pp, ((0, block - m), (0, 0)))
        q, r = jnp.asarray(ids[pp[:, 0]]), jnp.asarray(ids[pp[:, 1]])
        lq, lr = jnp.asarray(lens[pp[:, 0]]), jnp.asarray(lens[pp[:, 1]])
        if kind == "dp":
            got = _dp_block(q, r, lq, lr, gap=gap, low=low)
        else:
            got = _ungapped_block(q, r, lq, lr, low=low)
        out[s:s + m] = np.asarray(got)[:m]
    return out


def components(n: int, edges: np.ndarray) -> np.ndarray:
    """Connected components; each node labelled by its smallest member."""
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in np.asarray(edges).tolist():
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(x) for x in range(n)], np.int64)


def canonical(labels: np.ndarray) -> np.ndarray:
    """Relabel a partition by each part's smallest member."""
    labels = np.asarray(labels)
    first = {}
    for i, lab in enumerate(labels.tolist()):
        first.setdefault(lab, i)
    return np.array([first[lab] for lab in labels.tolist()], np.int64)


def allpairs(ids, lens, cfg: dict, *, low: bool = False) -> dict:
    """The whole clustering: pairs, prefilter and DP scores, families."""
    lsh = cfg["lsh"]
    sigs, valid = signatures(ids, lens, k=lsh["k"], T=lsh["T"], f=lsh["f"],
                             scheme=lsh["scheme"], low=low)
    pairs = candidate_pairs(sigs, valid, f=lsh["f"], bands=lsh["d"] + 1,
                            d=lsh["d"])
    ung = pair_scores(ids, lens, pairs, kind="ungapped", low=low)
    kept = ung >= cfg["prefilter_min"]
    scores = ung.copy()
    scores[kept] = pair_scores(ids, lens, pairs[kept], kind="dp",
                               gap=cfg["gap"], low=low)
    edges = pairs[scores >= cfg["min_score"]]
    return dict(pairs=pairs, ungapped=ung, kept=kept, scores=scores,
                labels=components(len(lens), edges))


# ------------------------------------------------------------ serving
@functools.partial(jax.jit, static_argnames=("k",))
def _answer_block(q, qv, refs, rv, masks, *, k: int):
    x = q[:, None, :] ^ refs[None, :, :]                        # (b, N, w)
    dist = jax.lax.population_count(x).astype(jnp.int32).sum(-1)
    cand = jnp.zeros(dist.shape, bool)
    for bnd in range(masks.shape[0]):
        cand |= jnp.all((x & masks[bnd]) == 0, axis=-1)
    ok = cand & rv[None, :] & qv[:, None]
    N = refs.shape[0]
    big = jnp.iinfo(jnp.int32).max
    key = jnp.where(ok, dist * N + jnp.arange(N, dtype=jnp.int32)[None], big)
    # k smallest keys: per chunk of the references, then over the chunks
    chunk = 8192
    pad = (-N) % chunk
    key = jnp.pad(key, ((0, 0), (0, pad)), constant_values=big)
    part = -jax.lax.top_k(-key.reshape(key.shape[0], -1, chunk), k)[0]
    neg, _ = jax.lax.top_k(-part.reshape(key.shape[0], -1), k)
    key = -neg
    hit = key < big
    return jnp.where(hit, key % N, -1), jnp.where(hit, key // N, -1)


def answers(ref_sigs, ref_valid, q_sigs, q_valid, *, f: int, bands: int,
            k: int, block: int = 32):
    """(ids, dists) (Q, k) of every query, -1-padded."""
    if (f + 1) * len(ref_valid) >= 2 ** 31:
        raise ValueError("distance * N + id must fit int32")
    refs = jnp.asarray(ref_sigs)
    rv = jnp.asarray(ref_valid)
    masks = jnp.asarray(band_masks(f, bands))
    Q = len(q_valid)
    ids = np.zeros((Q, k), np.int32)
    dists = np.zeros((Q, k), np.int32)
    for s in range(0, Q, block):
        q = np.asarray(q_sigs[s:s + block])
        v = np.asarray(q_valid[s:s + block])
        m = len(v)
        q = np.pad(q, ((0, block - m), (0, 0)))
        v = np.pad(v, (0, block - m))
        a, b = _answer_block(jnp.asarray(q), jnp.asarray(v), refs, rv, masks,
                             k=k)
        ids[s:s + m] = np.asarray(a)[:m]
        dists[s:s + m] = np.asarray(b)[:m]
    return ids, dists
