"""Anti-diagonal ("wavefront") Smith-Waterman with affine (Gotoh) gaps.

The row wave of :mod:`repro.align.smith_waterman` resolves the within-row
gap dependency with a max-plus prefix scan (`lax.cummax`) per query row —
O(log L) depth per row, but a *scan* op per row that XLA:CPU executes as a
sequential pass. Sweeping the DP by **anti-diagonals** removes the prefix
scan entirely: every cell on diagonal c depends only on diagonals c-1 and
c-2, so one diagonal step is pure elementwise arithmetic over (Lq, B)
lanes. With lanes indexed by query row i (j = c - i), the three
predecessors of H[i, j] are

    H[i, j-1]   -> same lane, previous diagonal        (h1)
    H[i-1, j]   -> shifted lane, previous diagonal     (h1s = shift(h1))
    H[i-1, j-1] -> shifted lane, diagonal c-2          (h2s)

and a lane shift is a contiguous-axis concatenate. Affine gaps (Gotoh)
add the E/F gap lanes with the same structure:

    E_c = max(E_{c-1} + extend, H_{c-1} + open)           (gap along j)
    F_c = max(shift(F_{c-1}) + extend, shift(H_{c-1}) + open)
    H_c = max(0, shift(H_{c-2}) + s_c, E_c, F_c)

Convention: ``open`` is the cost of the FIRST gap residue, ``extend`` of
each further one — ``open == extend`` degenerates bit-exactly to the
linear-gap recurrence (H[i, j-1] >= E[i, j-1] at every cell, so the E/F
lanes never beat the direct 3-way max).

Three CPU-focused tricks make this beat the row wave on XLA:CPU:

* **Sentinel-baked int8 table.** The substitution table is int8 with the
  PAD row/col overwritten by ``SENT8`` (-100), so PAD masking costs no
  compare/select pass. Along any DP path i and j are monotone, so a path
  that enters a sentinel region (PAD tail, or the out-of-matrix cells the
  skew introduces) never leaves it; each sentinel cell contributes <= -100
  while every H stays >= 0, so sentinel-region cells never exceed the best
  valid cell — *scores* are bit-exact with the masked row wave (cell
  values inside PAD regions may differ; nothing reads them).
* **Pad-reshape skew.** The (Lq, Lr, B) substitution block is re-laid to
  (nd, Lq, B) with skew[c, i] = sub[i, c-i] by padding the j axis to
  nd+1 with SENT8 and reshaping — no gather; out-of-range j land in the
  pad cells automatically.
* **Chunked minimal-carry scan.** The diagonal sweep is a `lax.scan`
  carrying only (h1, h2s) (+ (e1, f1) for affine) in int16 lanes when the
  score bound allows, processing ``_DIAG_CHUNK`` diagonals per step to
  amortize XLA:CPU's per-step dispatch overhead. (k=2 is a measured
  optimum: k>=3 crosses an XLA:CPU fusion cliff and regresses 3-6x, as
  does `scan(unroll>1)`.)

All entries return device arrays without a host sync, the contract the
all-pairs scheduler's drain ring relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alphabet import ALPHABET_SIZE, BLOSUM62_PADDED, PAD
from ..obs import trace_sentinel
from .smith_waterman import GAP

GAP_OPEN = -11   # BLOSUM62 companion defaults (BLAST -11/-1)
GAP_EXTEND = -1
SENT8 = -100     # sentinel substitution score baked into the int8 table

# int8 BLOSUM62 with the PAD row/col at SENT8: masking by table lookup.
_BSENT = BLOSUM62_PADDED.astype(np.int8).copy()
_BSENT[PAD, :] = SENT8
_BSENT[:, PAD] = SENT8

_DIAG_CHUNK = 2  # diagonals per scan step (measured optimum on XLA:CPU)


def lane_dtype(Lq: int, Lr: int):
    """int16 lanes while 11*L < 2^14 (H <= 11*min(Lq, Lr), the largest
    BLOSUM62 diagonal, so carries and h2s + SENT8 stay exact and far from
    the int16 rails); int32 above. Static shapes make this part of the
    jit key."""
    return jnp.int16 if 11 * max(Lq, Lr) < (1 << 14) else jnp.int32


def _sub_block(qs, rs):
    """(B, Lq) x (B, Lr) int8 -> (Lq, Lr, B) int8 substitution scores with
    SENT8 on every PAD row/col. One small gather builds the per-position
    reference profile B[:, r]; the query-symbol axis is resolved by 20
    selects (PAD falls through to the SENT8 default) — on XLA:CPU this is
    ~3x cheaper than the (Lq, Lr, B) two-axis gather."""
    table = jnp.asarray(_BSENT)
    rT = rs.T.astype(jnp.int32)                    # (Lr, B)
    rprof = table[:, rT]                           # (A+1, Lr, B) int8
    qT = qs.T                                      # (Lq, B) int8
    Lq, B = qT.shape
    Lr = rT.shape[0]
    out = jnp.broadcast_to(jnp.asarray(SENT8, jnp.int8), (Lq, Lr, B))
    for a in range(ALPHABET_SIZE):
        out = jnp.where((qT == a)[:, None, :], rprof[a][None], out)
    return out


def _skew_flat(sub):
    """(Lq, Lr, B) -> (nd, Lq, B) with out[c, i, b] = sub[i, c-i, b];
    out-of-range j = c-i read SENT8. Implemented by padding the j axis to
    nd+1 and reshaping (each row's start shifts by one slot per query
    row) — no gather."""
    Lq, Lr, B = sub.shape
    nd = Lq + Lr - 1
    w = jnp.pad(sub, ((0, 0), (0, nd + 1 - Lr), (0, 0)),
                constant_values=SENT8)             # (Lq, nd+1, B)
    sk = w.reshape(Lq * (nd + 1), B)[: Lq * nd].reshape(Lq, nd, B)
    return jnp.transpose(sk, (1, 0, 2))            # (nd, Lq, B)


def _skew(sub, k: int):
    """Chunked skew for the scan: (Lq, Lr, B) -> (ceil(nd/k), k, Lq, B),
    the tail diagonal group padded with SENT8 rows (inert, see module
    docstring)."""
    sk = _skew_flat(sub)
    nd, Lq, B = sk.shape
    pad = (-nd) % k
    if pad:
        sk = jnp.concatenate(
            [sk, jnp.full((pad, Lq, B), SENT8, jnp.int8)], axis=0)
    return sk.reshape(-1, k, Lq, B)


def _scan_linear(sk, gap: int, dt):
    """Linear-gap diagonal sweep over a skewed block; carries (h1, h2s)."""
    _, k, Lq, B = sk.shape
    z = jnp.zeros((Lq, B), dt)
    g = jnp.asarray(gap, dt)
    zrow = jnp.zeros((1, B), dt)

    def step(carry, srows):
        h1, h2s = carry
        m = None
        for t in range(k):
            h1s = jnp.concatenate([zrow, h1[:-1]], axis=0)
            h = jnp.maximum(jnp.maximum(h2s + srows[t].astype(dt), 0),
                            jnp.maximum(h1, h1s) + g)
            m = h if m is None else jnp.maximum(m, h)
            h1, h2s = h, h1s
        return (h1, h2s), jnp.max(m, axis=0)

    _, ms = jax.lax.scan(step, (z, z), sk)
    return jnp.max(ms, axis=0).astype(jnp.int32)


def _scan_affine(sk, gap_open: int, gap_extend: int, dt):
    """Gotoh diagonal sweep; carries (h1, h2s, e1, f1), all zero-init.

    The true E/F boundary is -inf; starting the gap lanes at 0 instead
    pollutes them with max(E_true, small-negative): since every H >= 0,
    E >= H + open >= open at every cell, so the polluted branch is the
    decaying chain extend*k, which is < 0 and can never win the 4-way max
    for H (H has a 0 floor). H — and therefore the score — is bit-exact
    with the -inf-boundary oracle (`kernels.ref.sw_affine_ref`).
    """
    _, k, Lq, B = sk.shape
    z = jnp.zeros((Lq, B), dt)
    go = jnp.asarray(gap_open, dt)
    ge = jnp.asarray(gap_extend, dt)
    zrow = jnp.zeros((1, B), dt)

    def shift(x):
        return jnp.concatenate([zrow, x[:-1]], axis=0)

    def step(carry, srows):
        h1, h2s, e1, f1 = carry
        m = None
        for t in range(k):
            h1s = shift(h1)
            e = jnp.maximum(e1 + ge, h1 + go)
            f = jnp.maximum(shift(f1) + ge, h1s + go)
            h = jnp.maximum(jnp.maximum(h2s + srows[t].astype(dt), 0),
                            jnp.maximum(e, f))
            m = h if m is None else jnp.maximum(m, h)
            h1, h2s, e1, f1 = h, h1s, e, f
        return (h1, h2s, e1, f1), jnp.max(m, axis=0)

    _, ms = jax.lax.scan(step, (z, z, z, z), sk)
    return jnp.max(ms, axis=0).astype(jnp.int32)


def _wave_linear_impl(qs, rs, gap: int):
    dt = lane_dtype(qs.shape[1], rs.shape[1])
    return _scan_linear(_skew(_sub_block(qs, rs), _DIAG_CHUNK), gap, dt)


def _wave_affine_impl(qs, rs, gap_open: int, gap_extend: int):
    dt = lane_dtype(qs.shape[1], rs.shape[1])
    return _scan_affine(_skew(_sub_block(qs, rs), _DIAG_CHUNK),
                        gap_open, gap_extend, dt)


@functools.partial(jax.jit, static_argnames=("gap",))
@trace_sentinel("wave_linear")
def _wave_linear(qs, rs, *, gap: int):
    return _wave_linear_impl(qs, rs, gap)


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend"))
@trace_sentinel("wave_affine")
def _wave_affine(qs, rs, *, gap_open: int, gap_extend: int):
    return _wave_affine_impl(qs, rs, gap_open, gap_extend)


def sw_wave_linear(qs, rs, *, gap: int = GAP) -> jax.Array:
    """Batched linear-gap SW scores via the wavefront sweep: (B, Lq) x
    (B, Lr) int8 (PAD-padded) -> (B,) int32 on device. Scores bit-exact
    with the per-pair row wave (`align.smith_waterman.sw_score`)."""
    return _wave_linear(jnp.asarray(qs), jnp.asarray(rs), gap=gap)


def sw_wave_affine(qs, rs, *, gap_open: int = GAP_OPEN,
                   gap_extend: int = GAP_EXTEND) -> jax.Array:
    """Batched affine-gap (Gotoh) SW scores via the wavefront sweep:
    (B, Lq) x (B, Lr) int8 -> (B,) int32 on device; bit-exact with the
    numpy oracle `kernels.ref.sw_affine_ref` on the unpadded pairs."""
    return _wave_affine(jnp.asarray(qs), jnp.asarray(rs),
                        gap_open=gap_open, gap_extend=gap_extend)
