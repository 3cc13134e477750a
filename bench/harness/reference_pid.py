"""Plain reference for percent-identity clustering, from the published
definitions. Nothing here imports the program or reads what it made.

* Pairs: the band join of ``reference.py`` (signatures, band agreement
  within Hamming d).
* Alignment: the int32 linear-gap BLOSUM62 Smith-Waterman matrix H of
  each pair, computed on the device in blocks of one padded shape, one
  row of H per step, and copied to the host.
* Walk: from the first maximum of H in row-major order (``np.argmax``)
  the walk goes back while i > 0, j > 0 and H > 0: diagonally if
  H[i,j] = H[i-1,j-1] + s(q_i, r_j), else up if H[i,j] = H[i-1,j] + gap,
  else left. Each step adds one to the alignment length; a diagonal step
  between equal residues adds one identity. PID = 100 * identities /
  max(length, 1). The walk runs backward in numpy, all pairs of a block
  in step.
* Families: connected components of the pairs with PID >= min_pid.

``low=True`` computes H in bfloat16 instead of int32 and walks it in
float32: the control that a correct comparison must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .reference import A, BLOSUM62, _sub_rows

QUANTUM = 128           # padded lengths of a block's shape
MAX_CELLS = 1 << 24     # H cells of one block on the device


@functools.partial(jax.jit, static_argnames=("gap", "low"))
def _h_block(q, r, lq, lr, *, gap: int, low: bool):
    """(B, Lq+1, Lr+1) H per pair, row and column 0 zero; cells past a
    pair's lengths read -1, so no argmax lands there."""
    dt = jnp.bfloat16 if low else jnp.int32
    B, Lr = r.shape
    idx = jnp.arange(Lr, dtype=dt)
    gap_d = jnp.asarray(gap, dt)

    def step(Hp, i):
        s = _sub_rows(q, r, lq, lr, i).astype(dt)
        h0 = jnp.maximum(jnp.maximum(Hp[:, :-1] + s, Hp[:, 1:] + gap_d),
                         jnp.zeros((), dt))
        H = jax.lax.cummax(h0 - gap_d * idx, axis=1) + gap_d * idx
        Hp = jnp.concatenate([jnp.zeros((B, 1), dt), H], axis=1)
        return Hp, Hp

    H0 = jnp.zeros((B, Lr + 1), dt)
    _, rows = jax.lax.scan(step, H0, jnp.arange(q.shape[1]))
    H = jnp.transpose(jnp.concatenate([H0[None], rows], axis=0), (1, 0, 2))
    ii = jnp.arange(q.shape[1] + 1)[None, :, None]
    jj = jnp.arange(Lr + 1)[None, None, :]
    inside = (ii <= lq[:, None, None]) & (jj <= lr[:, None, None])
    out = H.astype(jnp.float32 if low else jnp.int32)
    return jnp.where(inside, out, -1)


def walk(H, q, r, gap: int):
    """Walk every pair of a block back from its first best cell.
    H (B, Lq+1, Lr+1), q (B, Lq), r (B, Lr) -> (score, identities,
    length), each (B,)."""
    B = H.shape[0]
    b = np.arange(B)
    flat = H.reshape(B, -1)
    i, j = np.divmod(flat.argmax(axis=1), H.shape[2])
    score = H[b, i, j]
    ident = np.zeros(B, np.int64)
    length = np.zeros(B, np.int64)
    live = (i > 0) & (j > 0) & (score > 0)
    while live.any():
        im, jm = np.maximum(i - 1, 0), np.maximum(j - 1, 0)
        a, c = q[b, im].astype(np.int64), r[b, jm].astype(np.int64)
        s = BLOSUM62[np.minimum(a, A - 1), np.minimum(c, A - 1)]
        h = H[b, i, j]
        diag = h == H[b, im, jm] + s
        up = ~diag & (h == H[b, im, j] + gap)
        ident += live & diag & (a == c)
        length += live
        i = np.where(live & (diag | up), im, i)
        j = np.where(live & ~up, jm, j)
        live &= (i > 0) & (j > 0) & (H[b, i, j] > 0)
    return score, ident, length


def pid_scores(ids, lens, pairs, *, gap: int = -4, low: bool = False):
    """Score, PID and alignment length of every pair (P, 2)."""
    P = len(pairs)
    score = np.zeros(P, np.int32)
    pid = np.zeros(P)
    aln = np.zeros(P, np.int64)
    if P == 0:
        return score, pid, aln
    lens = np.asarray(lens, np.int32)
    L = -(-int(lens.max()) // QUANTUM) * QUANTUM
    ids = np.asarray(ids)
    if ids.shape[1] < L:
        ids = np.pad(ids, ((0, 0), (0, L - ids.shape[1])), constant_values=A)
    lq, lr = lens[pairs[:, 0]], lens[pairs[:, 1]]
    kq = -(-lq // QUANTUM) * QUANTUM
    kr = -(-lr // QUANTUM) * QUANTUM
    for Lq, Lr in sorted(set(zip(kq.tolist(), kr.tolist()))):
        rows = np.flatnonzero((kq == Lq) & (kr == Lr))
        Bk = max(1, min(256, MAX_CELLS // ((Lq + 1) * (Lr + 1))))
        for s in range(0, len(rows), Bk):
            sel = rows[s:s + Bk]
            pp = np.pad(pairs[sel], ((0, Bk - len(sel)), (0, 0)))
            q, r = ids[pp[:, 0], :Lq], ids[pp[:, 1], :Lr]
            H = np.asarray(_h_block(
                jnp.asarray(q), jnp.asarray(r), jnp.asarray(lens[pp[:, 0]]),
                jnp.asarray(lens[pp[:, 1]]), gap=gap, low=low))
            sc, idn, ln = walk(H, q, r, gap)
            m = len(sel)
            score[sel] = sc[:m]
            aln[sel] = ln[:m]
            pid[sel] = 100.0 * idn[:m] / np.maximum(ln[:m], 1)
    return score, pid, aln


def allpairs_pid(ids, lens, cfg: dict, *, low: bool = False) -> dict:
    """The whole clustering: pairs, DP scores, PID, alignment lengths,
    families."""
    lsh = cfg["lsh"]
    sigs, valid = reference.signatures(ids, lens, k=lsh["k"], T=lsh["T"],
                                       f=lsh["f"], scheme=lsh["scheme"],
                                       low=low)
    pairs = reference.candidate_pairs(sigs, valid, f=lsh["f"],
                                      bands=lsh["d"] + 1, d=lsh["d"])
    scores, pid, aln = pid_scores(ids, lens, pairs, gap=cfg["gap"], low=low)
    edges = pairs[pid >= cfg["min_pid"]]
    return dict(pairs=pairs, scores=scores, pid=pid, aln_len=aln,
                labels=reference.components(len(lens), edges))
