"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hamming_dist_ref(q, r) -> jnp.ndarray:
    """(Q, nw) x (R, nw) uint32 -> (Q, R) int32."""
    x = q[:, None, :] ^ r[None, :, :]
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def hamming_count_ref(q, r, d: int) -> jnp.ndarray:
    """(Q, nw) x (R, nw) -> (Q, 1) int32 counts of refs within distance d."""
    dist = hamming_dist_ref(q, r)
    return jnp.sum((dist <= d).astype(jnp.int32), axis=-1, keepdims=True)


def siggen_accumulate_ref(rows, cb, H, T: int) -> jnp.ndarray:
    """(S, D) x (W, D) x (W, f) -> (S, f) int32 SimHash accumulators."""
    scores = rows.astype(jnp.int32) @ cb.astype(jnp.int32).T   # (S, W)
    wts = jnp.where(scores >= T, scores, 0)
    return wts @ H.astype(jnp.int32)                           # (S, f)


def sw_affine_ref(q, r, gap_open: int = -11, gap_extend: int = -1):
    """Host Gotoh oracle: best local alignment score of one encoded pair
    (unpadded int8 arrays) under affine gaps, walking every cell of the
    three-lane DP. Convention: ``gap_open`` is the cost of the FIRST gap
    residue and ``gap_extend`` of each further one, so
    ``gap_open == gap_extend`` degenerates exactly to the linear-gap SW
    recurrence of ``align.smith_waterman`` (cell-exact on H).

    Returns (best_score, H) with H the (Lq+1, Lr+1) int64 DP matrix.
    """
    import numpy as np

    from ..core.alphabet import BLOSUM62_PADDED

    q = np.asarray(q, np.int64)
    r = np.asarray(r, np.int64)
    # plain Python rows: a cell costs ~0.5 us instead of ~4 us of numpy
    # scalar indexing, so a few hundred full-length pairs stay cheap
    sub = BLOSUM62_PADDED[q][:, r].astype(np.int64).tolist()
    Lq, Lr = len(q), len(r)
    NEGI = -(1 << 40)           # true -inf boundary for the gap lanes
    H = [[0] * (Lr + 1)]
    f_prev = [NEGI] * (Lr + 1)  # F of the previous row
    best = 0
    for i in range(1, Lq + 1):
        h_up, s_row = H[-1], sub[i - 1]
        h = [0] * (Lr + 1)
        f = [NEGI] * (Lr + 1)
        e = NEGI                # E of the previous cell in this row
        hj = 0                  # H of the previous cell in this row
        for j in range(1, Lr + 1):
            e = max(e + gap_extend, hj + gap_open)
            fj = max(f_prev[j] + gap_extend, h_up[j] + gap_open)
            hj = max(0, h_up[j - 1] + s_row[j - 1], e, fj)
            f[j] = fj
            h[j] = hj
            if hj > best:
                best = hj
        H.append(h)
        f_prev = f
    return best, np.asarray(H, np.int64)


def spgemm_upper_ref(offsets, ids, cap: int):
    """Host oracle for the upper-mask SpGEMM emission of ONE band: walk the
    bucket CSR and enumerate each unordered within-bucket pair once, in
    entry-major slot order — (cap, 2) int32, -1 past the true count.
    Independent of the jnp/Pallas implementations (plain loops)."""
    import numpy as np

    offsets = np.asarray(offsets)
    ids = np.asarray(ids)
    out = np.full((cap, 2), -1, np.int32)
    n = 0
    for u in range(len(offsets) - 1):
        members = ids[offsets[u]:offsets[u + 1]]
        for a in range(len(members)):
            for b in range(a + 1, len(members)):
                i, j = int(members[a]), int(members[b])
                out[n] = (min(i, j), max(i, j))
                n += 1
    return out


def ungapped_xdrop_ref(q, r, x: int) -> int:
    """Host oracle for the ungapped X-drop diagonal scan: one encoded pair
    (unpadded int8 arrays), walking every diagonal cell-by-cell with the
    exact restart rule of ``align.smith_waterman._ungapped_pair``."""
    import numpy as np

    from ..core.alphabet import BLOSUM62_PADDED

    q = np.asarray(q, np.int64)
    r = np.asarray(r, np.int64)
    sub = BLOSUM62_PADDED[q][:, r].astype(np.int64).tolist()
    best = 0
    for k in range(-(len(q) - 1), len(r)):
        i0, j0 = (max(0, -k), max(0, k))
        cur, rbest = 0, 0
        for t in range(min(len(q) - i0, len(r) - j0)):
            c = cur + sub[i0 + t][j0 + t]
            if c <= 0 or rbest - c > x:
                c, rbest = 0, 0
            elif c > rbest:
                rbest = c
            if c > best:
                best = c
            cur = c
    return best
