"""SimHash signature generation (paper §3 / Algorithm 2), TPU-native.

Two mathematically identical execution paths:

* ``method="matmul"`` — the paper's structure on the MXU: per block of
  codebook words, score shingles against the word block (matmul), threshold
  at T, multiply by the ±1 hyperplane block H[w, :f] and accumulate V.

* ``method="table"`` — beyond-paper: because the neighbour set and scores of
  a shingle depend only on its word id, the *total* contribution of a shingle
  to V is a pure function of that id. We precompute
      C[p] = sum_w [score(p,w) >= T] * score(p,w) * H[w]      (W, f) int32
  once per (k, T, f); signature generation then collapses to a gather +
  segment-sum over shingle ids — O(S) per sequence instead of O(S*W).
  (BLAST itself precomputes its neighbourhood lookup; this is the same trick
  lifted to the hyperplane domain.)

Hash-bit sources for the hyperplanes:
* ``scheme="java"`` — faithful: Java ``String.hashCode`` of the word's
  letters (polynomial-31, int32 wraparound), f <= 32 (paper used f=32).
* ``scheme="splitmix"`` — beyond-paper: splitmix64 chain over the word id,
  arbitrary f; better bit entropy (the Java hash's high bits are skewed for
  short words — measured in benchmarks/quality.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .alphabet import ALPHABET_SIZE, AMINO_ACIDS
from .neighbors import codebook, codebook_onehot
from .shingle import extract_shingles, shingle_ids

GOLDEN = np.uint64(0x9E3779B97F4A7C15)


# ---------------------------------------------------------------- hash bits
def java_hash(k: int) -> np.ndarray:
    """Java String.hashCode of every codebook word: (W,) int32 (wraparound)."""
    cb = codebook(k)  # (W, k) int8 ids
    chars = np.array([ord(c) for c in AMINO_ACIDS], dtype=np.uint32)
    h = np.zeros(cb.shape[0], dtype=np.uint32)
    for i in range(k):
        h = h * np.uint32(31) + chars[cb[:, i].astype(np.int64)]
    return h.view(np.int32)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + GOLDEN).astype(np.uint64)
    z = x
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


@functools.lru_cache(maxsize=16)
def hyperplanes(k: int, f: int, scheme: str = "java") -> np.ndarray:
    """±1 hyperplane matrix H (W, f) int8 — bit j of hash(word) picks the sign."""
    W = ALPHABET_SIZE**k
    if scheme == "java":
        if f > 32:
            raise ValueError("java hashCode provides 32 bits; use scheme='splitmix'")
        h = java_hash(k).view(np.uint32)
        bits = ((h[:, None] >> np.arange(f, dtype=np.uint32)) & 1).astype(np.int8)
    elif scheme == "splitmix":
        n64 = (f + 63) // 64
        ids = np.arange(W, dtype=np.uint64)
        words = np.stack(
            [_splitmix64(ids * np.uint64(n64) + np.uint64(r)) for r in range(n64)],
            axis=-1,
        )  # (W, n64) uint64
        all_bits = (
            (words[:, :, None] >> np.arange(64, dtype=np.uint64)) & np.uint64(1)
        ).astype(np.int8)
        bits = all_bits.reshape(W, n64 * 64)[:, :f]
    else:
        raise ValueError(f"unknown hash scheme {scheme!r}")
    return (bits * 2 - 1).astype(np.int8)  # {0,1} -> {-1,+1}


# ---------------------------------------------------------------- packing
def pack_bits(bits) -> jnp.ndarray:
    """(..., f) bool/int -> (..., f//32) uint32 little-endian bit packing."""
    f = bits.shape[-1]
    assert f % 32 == 0, "f must be a multiple of 32"
    b = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], f // 32, 32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1).astype(jnp.uint32)


def unpack_bits(packed, f: int) -> jnp.ndarray:
    """(..., f//32) uint32 -> (..., f) int32 in {0,1}."""
    w = packed[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)
    return (w & 1).astype(jnp.int32).reshape(*packed.shape[:-1], f)


# ---------------------------------------------------------------- contribution table
@functools.lru_cache(maxsize=8)
def contribution_table(k: int, T: int, f: int, scheme: str = "java") -> np.ndarray:
    """C[p] = Σ_w [score(p,w) >= T]·score(p,w)·H[w]  — (W, f) int32.

    Computed blockwise with numpy (one-off, cacheable); identical semantics to
    the matmul path (verified in tests/test_simhash.py).
    """
    cb_oh = codebook_onehot(k).astype(np.int32)  # (W, k*(A+1))
    from .alphabet import BLOSUM62_PADDED

    B = BLOSUM62_PADDED  # (21, 21)
    cb = codebook(k).astype(np.int64)  # (W, k)
    # rows[p] = concat_i B[p_i, :] -> (W, k*(A+1))
    rows = B[cb].reshape(cb.shape[0], -1).astype(np.int32)
    H = hyperplanes(k, f, scheme).astype(np.int32)  # (W, f)
    W_total = cb.shape[0]
    out = np.zeros((W_total, f), dtype=np.int32)
    blk = 4096
    # float32 BLAS is exact here: |score| <= 44, |V| < 2^24 — and ~100x
    # faster than numpy's unaccelerated integer matmul (k=4 is a one-off
    # 160k x 160k sweep).
    rows_f = rows.astype(np.float32)
    cb_f = cb_oh.T.astype(np.float32)
    H_f = H.astype(np.float32)
    for i in range(0, W_total, blk):
        scores = rows_f[i : i + blk] @ cb_f          # (blk, W)
        wts = np.where(scores >= T, scores, 0.0)
        out[i : i + blk] = (wts @ H_f).astype(np.int32)
    return out


# ---------------------------------------------------------------- signature gen
def signatures_matmul(ids, lengths, *, k: int, T: int, f: int,
                      scheme: str = "java", word_block: int = 4096):
    """Paper-structure path: V = Σ_shingles thresholded-scores @ H, blocked
    over the codebook so the (S, W) score matrix never hits HBM whole.

    Args:
      ids: (N, L) int8 padded residues;  lengths: (N,).
    Returns:
      packed signatures (N, f//32) uint32.
    """
    from .neighbors import shingle_rows

    sh, mask = extract_shingles(ids, lengths, k)        # (N, S, k), (N, S)
    rows = shingle_rows(sh)                              # (N, S, k*(A+1)) int32
    rows = rows * mask[..., None].astype(jnp.int32)
    N, S, D = rows.shape
    C = jnp.asarray(codebook_onehot(k), jnp.int32)       # (W, D)
    H = jnp.asarray(hyperplanes(k, f, scheme), jnp.int32)  # (W, f)
    Wt = C.shape[0]
    nblk = -(-Wt // word_block)
    pad = nblk * word_block - Wt
    Cp = jnp.pad(C, ((0, pad), (0, 0))).reshape(nblk, word_block, D)
    Hp = jnp.pad(H, ((0, pad), (0, 0))).reshape(nblk, word_block, f)

    def body(V, blk):
        Cb, Hb = blk
        scores = jnp.einsum("nsd,wd->nsw", rows, Cb)     # (N, S, wb)
        wts = jnp.where(scores >= T, scores, 0)
        V = V + jnp.einsum("nsw,wf->nf", wts, Hb)        # accumulate
        return V, None

    V0 = jnp.zeros((N, f), jnp.int32)
    V, _ = jax.lax.scan(body, V0, (Cp, Hp))
    return pack_bits(V >= 0)


def signatures_table(ids, lengths, *, k: int, T: int, f: int,
                     scheme: str = "java", table=None):
    """Beyond-paper path: signature = pack(Σ_s C[shingle_id(s)] >= 0)."""
    if table is None:
        table = contribution_table(k, T, f, scheme)
    Ct = jnp.asarray(table)                              # (W, f) int32
    sh, mask = extract_shingles(ids, lengths, k)
    wid = shingle_ids(sh)                                # (N, S), -1 invalid
    contrib = jnp.where(wid[..., None] >= 0, Ct[jnp.maximum(wid, 0)], 0)
    V = jnp.sum(contrib, axis=1)                         # (N, f)
    return pack_bits(V >= 0)


def signatures(ids, lengths, *, k: int = 3, T: int = 13, f: int = 32,
               scheme: str = "java", method: str = "table", **kw):
    fn = {"table": signatures_table, "matmul": signatures_matmul}[method]
    return fn(ids, lengths, k=k, T=T, f=f, scheme=scheme, **kw)


@functools.lru_cache(maxsize=8)
def feature_count_table(k: int, T: int) -> np.ndarray:
    """count[p] = #{w : score(p, w) >= T} — neighbours per parent word."""
    from .alphabet import BLOSUM62_PADDED
    cb_oh = codebook_onehot(k).astype(np.float32)
    cb = codebook(k).astype(np.int64)
    rows = BLOSUM62_PADDED[cb].reshape(cb.shape[0], -1).astype(np.float32)
    W = cb.shape[0]
    out = np.zeros((W,), np.int32)
    blk = 4096
    for i in range(0, W, blk):
        scores = rows[i:i + blk] @ cb_oh.T
        out[i:i + blk] = (scores >= T).sum(axis=1)
    return out


def feature_counts(ids, lengths, *, k: int, T: int) -> jnp.ndarray:
    """Per-sequence total neighbour-feature count. The paper's Signature
    Processor "is designed to process only the sequences with non-zero
    signatures" (§5.2): sequences with zero features collapse to the
    all-ones fingerprint (V=0 -> every bit set) and must be filtered."""
    table = jnp.asarray(feature_count_table(k, T))
    sh, mask = extract_shingles(ids, lengths, k)
    wid = shingle_ids(sh)
    cnt = jnp.where(wid >= 0, table[jnp.maximum(wid, 0)], 0)
    return jnp.sum(cnt, axis=1)
