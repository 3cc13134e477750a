"""Smith-Waterman local alignment in JAX + host-side traceback for PID.

The paper evaluates result quality by the *percent identity* (PID) of the
alignment of each emitted (query, reference) pair (§5.2). The DP runs
on-device as a *row wave*: with a linear gap penalty the within-row
dependency

    H[i,j] = max(A[j], H[i,j-1] + GAP),
    A[j]   = max(0, H[i-1,j-1] + s[i,j], H[i-1,j] + GAP)

has the closed form  H[i,j] = max_{t<=j} (A[t] + GAP*(j-t)), a max-plus
prefix scan:  H[i,1:] = cummax(A + c*t) - c*t  with c = -GAP.  (A >= 0 makes
the max(0, .) clamp automatic.)  Each row is therefore one vectorized cummax
over the reference axis instead of a sequential column scan — the whole DP
is a single `lax.scan` over query rows, vmapped over pairs, so a (B, Lq, Lr)
pair block scores in one jitted program (the "SW wave" the all-pairs tiler
dispatches).  Cell values are integer and identical to the classic
recurrence, so scores, DP matrices, and tracebacks are bit-exact with the
per-pair path.

The O(L) traceback that extracts matched positions runs host-side in numpy
(pairs to trace are few; the DP is the hot part).

Linear gap penalty (the paper's quality analysis uses ungapped/simple-gap
BLAST alignments; gap open == extend keeps the DP a 3-way max).

The anti-diagonal *wavefront* sweep (:mod:`repro.align.gotoh`) has since
superseded this row wave as the default score-only kernel
(``dp_kernel="wavefront"``, ~2.8x on CPU, affine gaps supported); the row
wave remains the ``"rowwave"`` fallback and the PID/matrix path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alphabet import BLOSUM62_PADDED, PAD
from ..obs import trace_sentinel

GAP = -4     # linear gap penalty (BLOSUM62-compatible default)
NEG = -10**6  # masked-substitution sentinel (padded positions never win)


def _sub_matrix(q, r):
    """(Lq,) x (Lr,) int8 -> (Lq, Lr) int32 substitution scores,
    PAD-masked (a masked cell can never win the 3-way max)."""
    B = jnp.asarray(BLOSUM62_PADDED)
    sub = B[q.astype(jnp.int32)][:, r.astype(jnp.int32)]
    valid = (q[:, None] != PAD) & (r[None, :] != PAD)
    return jnp.where(valid, sub, NEG)


def _wave_row(prev_row, sub_row):
    """One DP row via the max-plus prefix scan (see module docstring).

    prev_row: H[i-1, :] (Lr+1,);  sub_row: s[i, :] (Lr,), both int32.
    Returns H[i, :] (Lr+1,) int32, cell-exact with the classic recurrence.
    """
    c = jnp.int32(-GAP)
    a = jnp.maximum(0, jnp.maximum(prev_row[:-1] + sub_row,
                                   prev_row[1:] + GAP))
    t = jnp.arange(1, a.shape[0] + 1, dtype=jnp.int32)
    p = jax.lax.cummax(a + c * t)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), p - c * t])


@functools.partial(jax.jit, static_argnames=("return_matrix",))
def _sw_dp(q, r, return_matrix: bool = False):
    """One pair: q (Lq,) int8, r (Lr,) int8 (PAD-padded).

    Returns (best_score, H) where H is the (Lq+1, Lr+1) DP matrix if
    requested (int32), else a dummy scalar.

    Both paths are plain int32 scans. The row wave is the *fallback* DP
    (``dp_kernel="rowwave"``); the int16-carry + unrolled-scan variant it
    once had is retired — the anti-diagonal wavefront (`repro.align.gotoh`)
    replaced it as the fast path and the narrowing bought nothing on top
    of the int32 row wave worth its guard plumbing (1.13x, vs 2.8x for
    the wavefront; see ROADMAP "Perf ledger").
    """
    if return_matrix:
        sub = _sub_matrix(q, r)
        H0 = jnp.zeros(r.shape[0] + 1, jnp.int32)
        _, rows = jax.lax.scan(
            lambda prev, s: (lambda row: (row, row))(
                _wave_row(prev, s)),
            H0, sub)
        H = jnp.concatenate([H0[None], rows], axis=0)   # (Lq+1, Lr+1)
        return jnp.max(H), H
    # score-only: carry a running max instead of materializing H
    sub = _sub_matrix(q, r)
    H0 = jnp.zeros(r.shape[0] + 1, jnp.int32)

    def step(carry, s):
        prev, best = carry
        row = _wave_row(prev, s)
        return (row, jnp.maximum(best, jnp.max(row))), None

    (_, best), _ = jax.lax.scan(step, (H0, jnp.zeros((), jnp.int32)), sub)
    return best, jnp.int32(0)


def sw_score(q, r) -> int:
    """Best local alignment score of one encoded pair."""
    s, _ = _sw_dp(jnp.asarray(q), jnp.asarray(r))
    return int(s)


@jax.jit
def _sw_scores_batch(qs, rs):
    return jax.vmap(lambda a, b: _sw_dp(a, b)[0])(qs, rs)


def sw_scores_device(qs, rs) -> jax.Array:
    """Device-resident wave entry: (B, Lq) x (B, Lr) int8 device (or host)
    arrays -> (B,) int32 best scores, returned *on device* without a host
    sync — the all-pairs scheduler chains this behind its fused gather and
    drains results through an async ring (`repro.allpairs.tiles`)."""
    return _sw_scores_batch(qs, rs)


# ------------------------------------------------------------ device gather
def gather_rows(ids_dev, lens_dev, idx, L: int):
    """Fused wave gather: (N, Lmax) device corpus -> (B, L) PAD-masked block
    for row indices ``idx`` (idx < 0 marks padding slots -> all-PAD rows).
    The corpus is uploaded once; per-wave H2D traffic is just ``idx``."""
    safe = jnp.maximum(idx, 0)
    rows = ids_dev[safe, :min(L, ids_dev.shape[1])]
    if rows.shape[1] < L:       # padded ladder exceeds the corpus width
        rows = jnp.pad(rows, ((0, 0), (0, L - rows.shape[1])),
                       constant_values=PAD)
    ln = jnp.where(idx >= 0, lens_dev[safe], 0)
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    return jnp.where(pos < ln[:, None], rows, PAD)


def dp_scores_block(qm, rm, *, dp_kernel: str = "wavefront",
                    gap_mode: str = "linear", gap_open: int | None = None,
                    gap_extend: int | None = None) -> jax.Array:
    """Route a gathered (B, Lq) x (B, Lr) pair block to a DP sweep.

    ``dp_kernel`` picks the sweep order — ``"wavefront"`` (anti-diagonal,
    `repro.align.gotoh`, the fast default) or ``"rowwave"`` (the int32
    row-wave fallback, linear-gap only). ``gap_mode`` picks the penalty
    model — ``"linear"`` (scores identical under both kernels) or
    ``"affine"`` (Gotoh; wavefront-only). Traceable: safe to call under an
    enclosing jit with the knobs static.
    """
    if gap_mode not in ("linear", "affine"):
        raise ValueError(f"unknown gap_mode {gap_mode!r}")
    if dp_kernel not in ("wavefront", "rowwave"):
        raise ValueError(f"unknown dp_kernel {dp_kernel!r}")
    from .gotoh import GAP_EXTEND, GAP_OPEN, _wave_affine_impl, \
        _wave_linear_impl
    if gap_mode == "affine":
        if dp_kernel == "rowwave":
            raise ValueError("affine gaps need dp_kernel='wavefront' "
                             "(the row wave's prefix-scan closed form "
                             "only holds for linear penalties)")
        return _wave_affine_impl(
            qm, rm, GAP_OPEN if gap_open is None else gap_open,
            GAP_EXTEND if gap_extend is None else gap_extend)
    if dp_kernel == "rowwave":
        return _sw_scores_batch(qm, rm)
    return _wave_linear_impl(qm, rm, GAP if gap_open is None else gap_open)


@functools.partial(jax.jit, static_argnames=(
    "Lq", "Lr", "dp_kernel", "gap_mode", "gap_open", "gap_extend"))
@trace_sentinel("sw_gather")
def sw_gather_scores(q_ids, q_lens, r_ids, r_lens, qi, ri, *,
                     Lq: int, Lr: int, dp_kernel: str = "wavefront",
                     gap_mode: str = "linear", gap_open: int | None = None,
                     gap_extend: int | None = None) -> jax.Array:
    """ONE jitted program: gather both pair sides from device-resident
    corpora and run the full SW wave. (qi, ri) (B,) int32 with -1 padding;
    padding slots score 0. Used by the all-pairs scheduler (q_ids is r_ids)
    and the serving re-rank (queries vs the reference store). DP routing
    knobs are static (see :func:`dp_scores_block`); defaults — wavefront
    sweep, linear gaps — keep scores bit-exact with the historical
    row-wave path."""
    qm = gather_rows(q_ids, q_lens, qi, Lq)
    rm = gather_rows(r_ids, r_lens, ri, Lr)
    return dp_scores_block(qm, rm, dp_kernel=dp_kernel, gap_mode=gap_mode,
                           gap_open=gap_open, gap_extend=gap_extend)


# ------------------------------------------------------------ ungapped X-drop
_UNROLL = 16       # scan unroll: amortizes CPU per-step dispatch overhead
_INT16_MAX_L = 1024  # int16 carries are exact while 11*L + margins < 2^15


def _ungapped_pair(q, r, x: int | None, dtype):
    """Best X-drop-terminated ungapped diagonal run of one padded pair.

    Cell (i, j) extends the run of (i-1, j-1) on its diagonal:

        c[i,j] = cur[i-1,j-1] + s[i,j]

    and the run *restarts* (c -> 0, run-best -> 0) when it goes non-positive
    (Kadane's reset — local alignments never keep negative prefixes) or,
    with finite ``x``, when it X-drops: the run fell more than ``x`` below
    its own running best (BLAST's ungapped-extension termination rule). The
    returned score is the max of c over all cells; ``x=None`` is the x->inf
    limit — exactly the best ungapped local segment score (max-subarray per
    diagonal) — and drops the run-best carry from the recurrence.

    Indexing the carries by reference column j makes the diagonal
    predecessor a right-shift of the carry row, so each DP row is
    elementwise — no prefix scan — which (plus int16 lanes for short waves
    and an unrolled scan) is what makes this a cheap prefilter for the
    gapped wave.
    """
    # masked cells: any run is killed, yet cur + neg can't underflow dtype
    neg = dtype(-(1 << 14)) if dtype == jnp.int16 else jnp.int32(NEG)
    B = jnp.asarray(BLOSUM62_PADDED, dtype)
    sub = B[q.astype(jnp.int32)][:, r.astype(jnp.int32)]
    valid = (q[:, None] != PAD) & (r[None, :] != PAD)
    sub = jnp.where(valid, sub, neg)
    Lr = sub.shape[1]
    z = jnp.zeros(Lr, dtype)

    if x is None:
        def row(carry, s_row):
            cur, gbest = carry
            cur_s = jnp.concatenate([jnp.zeros(1, dtype), cur[:-1]])
            c = jnp.maximum(cur_s + s_row, 0)
            return (c, jnp.maximum(gbest, jnp.max(c))), None

        (_, best), _ = jax.lax.scan(row, (z, jnp.zeros((), dtype)), sub,
                                    unroll=_UNROLL)
    else:
        # any x above the max possible run score (11 * L) never triggers a
        # drop, so clamping keeps huge margins exact AND inside the dtype
        cap = (1 << 14) if dtype == jnp.int16 else (1 << 30)
        xv = dtype(min(int(x), cap))

        def row(carry, s_row):
            cur, rbest, gbest = carry
            cur_s = jnp.concatenate([jnp.zeros(1, dtype), cur[:-1]])
            rb_s = jnp.concatenate([jnp.zeros(1, dtype), rbest[:-1]])
            c = cur_s + s_row
            drop = (c <= 0) | (rb_s - c > xv)
            c = jnp.where(drop, 0, c).astype(dtype)
            rb = jnp.where(drop, 0, jnp.maximum(rb_s, c)).astype(dtype)
            return (c, rb, jnp.maximum(gbest, jnp.max(c))), None

        (_, _, best), _ = jax.lax.scan(
            row, (z, z, jnp.zeros((), dtype)), sub, unroll=_UNROLL)
    return best.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("x",))
def _ungapped_batch(qs, rs, x: int | None = None):
    small = max(qs.shape[1], rs.shape[1]) <= _INT16_MAX_L
    dtype = jnp.int16 if small else jnp.int32
    return jax.vmap(lambda q, r: _ungapped_pair(q, r, x, dtype))(qs, rs)


def ungapped_xdrop_scores(qs, rs, *, x: int | None = None) -> jax.Array:
    """Batched ungapped X-drop scores: (B, Lq) x (B, Lr) int8 -> (B,) int32,
    on device (no host sync). ``x=None`` disables the drop test (plain best
    ungapped segment, the max-recall and fastest setting). Always a lower
    bound of the gapped SW score, so thresholding on it never *adds* pairs —
    the all-pairs prefilter contract.
    """
    return _ungapped_batch(jnp.asarray(qs), jnp.asarray(rs), x)


@jax.jit
def _sw_batch_with_matrix(qs, rs):
    def one(q, r):
        best, H = _sw_dp(q, r, return_matrix=True)
        return best, H
    return jax.vmap(one)(qs, rs)


def sw_align_batch(qs, rs) -> np.ndarray:
    """Batched best-scores: (N, Lq) x (N, Lr) -> (N,) int32 (one jit call)."""
    return np.asarray(_sw_scores_batch(jnp.asarray(qs), jnp.asarray(rs)))


def _traceback_pid(H: np.ndarray, q: np.ndarray, r: np.ndarray,
                   sub: np.ndarray) -> tuple[float, int]:
    """Host traceback from argmax(H): returns (PID %, alignment length)."""
    i, j = np.unravel_index(np.argmax(H), H.shape)
    ident = 0
    length = 0
    while i > 0 and j > 0 and H[i, j] > 0:
        h = H[i, j]
        if h == H[i - 1, j - 1] + sub[i - 1, j - 1]:
            ident += int(q[i - 1] == r[j - 1])
            length += 1
            i, j = i - 1, j - 1
        elif h == H[i - 1, j] + GAP:
            length += 1
            i -= 1
        else:
            length += 1
            j -= 1
    return (100.0 * ident / max(length, 1), length)


def percent_identity(q, r) -> tuple[float, int, int]:
    """PID of the best local alignment of one encoded pair.

    Returns (pid_percent, alignment_length, score).
    """
    qj, rj = jnp.asarray(q), jnp.asarray(r)
    score, H = _sw_dp(qj, rj, return_matrix=True)
    B = BLOSUM62_PADDED
    qn, rn = np.asarray(q), np.asarray(r)
    sub = B[qn.astype(np.int64)][:, rn.astype(np.int64)]
    pid, length = _traceback_pid(np.asarray(H), qn, rn, sub)
    return pid, length, int(score)


def sw_wave_pid(qs, rs, *, chunk: int = 32):
    """Batched scores + PID: one jitted DP wave per chunk of pairs, then the
    host traceback per pair.

    qs (N, Lq) x rs (N, Lr) int8, PAD-padded (padding only ever suffixes a
    sequence, so the real subgrid of each padded DP matrix — and its argmax
    cell in row-major order — is identical to the unpadded one; results are
    bit-exact with :func:`percent_identity` on the unpadded pair). Device
    arrays are scored where they are; only the walk copies them to host.

    Returns (pid (N,) float64, length (N,) int64, score (N,) int64).
    All-PAD rows (wave padding) score 0 with pid 0, length 0.
    """
    if not isinstance(qs, jax.Array):
        qs, rs = np.asarray(qs, np.int8), np.asarray(rs, np.int8)
    N = qs.shape[0]
    pid = np.zeros(N)
    length = np.zeros(N, np.int64)
    score = np.zeros(N, np.int64)
    B = BLOSUM62_PADDED
    for i in range(0, N, chunk):
        qc, rc = qs[i:i + chunk], rs[i:i + chunk]
        sc, H = _sw_batch_with_matrix(jnp.asarray(qc), jnp.asarray(rc))
        qc, rc = np.asarray(qc), np.asarray(rc)
        Hn = np.asarray(H)
        sc = np.asarray(sc)
        for n in range(len(qc)):
            sub = B[qc[n].astype(np.int64)][:, rc[n].astype(np.int64)]
            p, l = _traceback_pid(Hn[n], qc[n], rc[n], sub)
            pid[i + n] = p
            length[i + n] = l
            score[i + n] = int(sc[n])
    return pid, length, score


def batch_percent_identity(pairs, q_ids, q_lens, r_ids, r_lens) -> np.ndarray:
    """PID for each (qi, ri) row of a pair buffer; invalid rows -> nan.

    Valid rows are gathered into padded blocks and scored as one DP wave per
    chunk (bit-exact with the per-pair path, just batched).
    """
    pairs = np.asarray(pairs)
    out = np.full(len(pairs), np.nan)
    rows = [(n, int(qi), int(ri)) for n, (qi, ri, *_) in enumerate(pairs)
            if qi >= 0]
    if not rows:
        return out
    Lq = int(max(q_lens[qi] for _, qi, _ in rows))
    Lr = int(max(r_lens[ri] for _, _, ri in rows))
    qm = np.full((len(rows), max(Lq, 1)), PAD, np.int8)
    rm = np.full((len(rows), max(Lr, 1)), PAD, np.int8)
    for n, (_, qi, ri) in enumerate(rows):
        qm[n, :int(q_lens[qi])] = q_ids[qi][:int(q_lens[qi])]
        rm[n, :int(r_lens[ri])] = r_ids[ri][:int(r_lens[ri])]
    pid, _, _ = sw_wave_pid(qm, rm)
    for n, (slot, _, _) in enumerate(rows):
        out[slot] = pid[n]
    return out
