"""Pallas TPU kernels: batched Smith-Waterman over a pair block — the
anti-diagonal (wavefront) Gotoh sweep, its percent-identity mode, the
ungapped X-drop prefilter, and the legacy row wave.

The all-pairs tiler's inner loop (`repro.allpairs.tiles`): score a block of
(query, reference) pairs in one program. The wavefront and ungapped
kernels share one body over the skewed substitution block of
`align.gotoh` (``sk[c, b, i] = s_b[i, c-i]``): lanes are query rows, so
every DP cell's predecessors sit on the two previous anti-diagonals and
one diagonal step is elementwise arithmetic over (bb, Lq) lanes — the
gapped step is the wavefront recurrence, the ungapped step keeps only the
diagonal move with BLAST's X-drop restart, and the PID step also carries
the identities and length of the walk back from each cell. The grid is
(pair block, diagonal block); the DP carries live in VMEM scratch across
the diagonal axis, so VMEM holds one (dc, bb, Lq) int8 slice of the
skewed block and the carries, whatever the pair length.

Everything is written in the subset Mosaic lowers: the per-diagonal
substitution row is a dynamic index on the ref's leading axis
(``sk_ref[c]``), and the one-lane shift is a lane rotate plus a mask
(``pltpu.roll``) — neither a ``dynamic_slice`` of a loaded value nor an
unaligned lane concatenate.

``interpret`` defaults to *autodetect*: kernels lower natively wherever the
backend supports Pallas TPU lowering and fall back to interpret mode only
where it is unavailable (the CPU). Pass ``interpret=True/False`` to
override (exposed as ``WaveConfig.pallas_interpret``).

Cell values are integer: scores are bit-exact with the jnp wavefront sweep
(`align.gotoh`), the jnp ungapped scan
(`align.smith_waterman.ungapped_xdrop_scores`) and the row wave
(`align.smith_waterman.sw_align_batch`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..align.gotoh import _BSENT, GAP_EXTEND, GAP_OPEN, SENT8
from ..align.smith_waterman import GAP, NEG
from ..core.alphabet import ALPHABET_SIZE, BLOSUM62_PADDED, PAD

DEFAULT_BB = 8
DIAG_BLOCK = 128        # anti-diagonals per grid step of the wave kernels


def on_tpu() -> bool:
    """True iff the default backend lowers Pallas TPU kernels natively."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Autodetect interpret mode: explicit override wins, otherwise
    interpret only where native Pallas lowering is unavailable."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def _shift_right(v, lane0):
    """``v`` moved one lane up the query axis (lane i reads lane i-1),
    zero-filled at lane 0."""
    return jnp.where(lane0, 0, pltpu.roll(v, 1, 1))


def _wave_kernel(sk_ref, *refs, mode: str, gap_open: int,
                 gap_extend: int, x: int | None, dc: int):
    """``dc`` diagonals of one (bb,) pair block. ``refs`` are the outputs
    (the best score; in ``pid`` mode also the packed walk of the best
    cell), then the carries: ``carry[0]`` is the running best, the rest
    the mode's DP lanes, all zero at the first diagonal block and written
    back to VMEM scratch after each one."""
    n_out = 2 if mode == "pid" else 1
    out_ref, carry = refs[0], refs[n_out:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for ref in carry:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, carry[0].shape, 1)
    lane0 = lane == 0

    def shift(v):
        return _shift_right(v, lane0)

    def step(c, st):
        s = sk_ref[c].astype(jnp.int32)       # (bb, Lq) diagonal c
        if mode == "linear":
            best, h1, h2s = st
            h1s = shift(h1)
            h = jnp.maximum(jnp.maximum(h2s + s, 0),
                            jnp.maximum(h1, h1s) + gap_open)
            return jnp.maximum(best, h), h, h1s
        if mode == "pid":
            # s = 2 * score + match (`_PID_TABLE`); t packs the walk that
            # starts at a cell as ident << 16 | length, and bt the walk of
            # each lane's first best cell
            best, h1, h2s, t1, t2s, bt = st
            h1s, t1s = shift(h1), shift(t1)
            diag = h2s + (s >> 1)
            up = h1s + gap_open
            h = jnp.maximum(jnp.maximum(diag, 0),
                            jnp.maximum(h1 + gap_open, up))
            t = jnp.where(h == diag, t2s + ((s & 1) << 16),
                          jnp.where(h == up, t1s, t1)) + 1
            t = jnp.where(h == 0, 0, t)
            return (jnp.maximum(best, h), h, h1s, t, t1s,
                    jnp.where(h > best, t, bt))
        if mode == "affine":
            best, h1, h2s, e1, f1 = st
            h1s = shift(h1)
            e = jnp.maximum(e1 + gap_extend, h1 + gap_open)
            f = jnp.maximum(shift(f1) + gap_extend, h1s + gap_open)
            h = jnp.maximum(jnp.maximum(h2s + s, 0), jnp.maximum(e, f))
            return jnp.maximum(best, h), h, h1s, e, f
        # ungapped: the run of (i-1, j-1) extends by s; a sentinel cell
        # (PAD or outside the matrix) restarts it like the masked jnp scan
        best, a1, a2s, *rb = st
        v = a2s + s
        drop = (v <= 0) | (s == SENT8)
        if x is None:
            v = jnp.where(drop, 0, v)
            return jnp.maximum(best, v), v, shift(a1)
        r1, r2s = rb
        drop = drop | (r2s - v > x)
        v = jnp.where(drop, 0, v)
        r = jnp.where(drop, 0, jnp.maximum(r2s, v))
        return jnp.maximum(best, v), v, shift(a1), r, shift(r1)

    st = jax.lax.fori_loop(0, dc, step, tuple(ref[...] for ref in carry))
    for ref, v in zip(carry, st):
        ref[...] = v

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _emit():
        best = jnp.max(st[0], axis=1, keepdims=True)
        out_ref[...] = best
        if mode == "pid":       # the first lane (query row) at the best
            first = jnp.min(jnp.where(st[0] == best, lane, lane.shape[1]),
                            axis=1, keepdims=True)
            refs[1][...] = jnp.max(jnp.where(lane == first, st[5], 0),
                                   axis=1, keepdims=True)


# XLA names each kernel's op after these (``ungapped_prefilter.1``), so a
# profile tells the kernels from the skew around them
_KERNEL_NAMES = {"linear": "wavefront_dp", "affine": "wavefront_dp",
                 "ungapped": "ungapped_prefilter", "pid": "wavefront_pid"}


def _wave_call(sk, *, mode: str, bb: int, interpret: bool | None,
               gap_open: int = 0, gap_extend: int = 0,
               x: int | None = None):
    """Run the shared wave kernel over a skewed (nd, B, Lq) int8 block ->
    (B, 1) int32 best scores, and in ``pid`` mode also (B, 1) int32 packed
    walks. The diagonal axis pads to a ``dc`` multiple with sentinel
    rows, which no score can come from (`align.gotoh`)."""
    nd, B, Lq = sk.shape
    assert B % bb == 0, "pad the pair block to a bb multiple"
    if x is not None:       # past 11 * L no run can drop; keep it int32
        x = min(int(x), 1 << 30)
    dc = min(DIAG_BLOCK, nd)
    pad = (-nd) % dc
    if pad:
        with jax.named_scope("skew"):
            sk = jnp.concatenate(
                [sk, jnp.full((pad, B, Lq), SENT8, jnp.int8)], axis=0)
    n_carry = {"affine": 5, "pid": 6}.get(mode, 3 if x is None else 5)
    out_spec = pl.BlockSpec((bb, 1), lambda i, k: (i, 0))
    out_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    if mode == "pid":
        out_spec, out_shape = [out_spec] * 2, [out_shape] * 2
    return pl.pallas_call(
        functools.partial(_wave_kernel, mode=mode, gap_open=gap_open,
                          gap_extend=gap_extend, x=x, dc=dc),
        grid=(B // bb, (nd + pad) // dc),
        in_specs=[pl.BlockSpec((dc, bb, Lq), lambda i, k: (k, i, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bb, Lq), jnp.int32)] * n_carry,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=_KERNEL_NAMES[mode],
    )(sk)


# The PID kernel's substitution table: 2 * BLOSUM62 + (residues equal),
# so one int8 cell carries the score (``>> 1``) and the match bit (``& 1``)
# through the same skew; PAD stays SENT8 (-50 once shifted, still a
# sentinel: no path through it can tie a real best)
_PID_TABLE = np.where(_BSENT == SENT8, SENT8,
                      2 * _BSENT.astype(np.int32)
                      + np.eye(ALPHABET_SIZE + 1, dtype=np.int32)
                      ).astype(np.int8)


@jax.named_scope("skew")
def _skewed(qs, rs, table=_BSENT):
    """(B, Lq) x (B, Lr) int8 -> the (nd, B, Lq) int8 skewed substitution
    block the wave kernels sweep, ``sk[c, b, i] = table[r_b[c-i], q_b[i]]``
    (SENT8 on PAD and outside the matrix). The reference is skewed by one
    gather of column ``c - i`` and the query axis resolved by 20 selects.
    This is bit-identical to `align.gotoh`'s pad-reshape skew, which the
    TPU compiler takes ~25 s per shape to lay out at L=640 (this form:
    ~2 s). Its ops sit under the ``skew`` name scope."""
    B, Lq = qs.shape
    Lr = rs.shape[1]
    nd = Lq + Lr - 1
    c = jax.lax.broadcasted_iota(jnp.int32, (nd, Lq), 0)
    j = c - jax.lax.broadcasted_iota(jnp.int32, (nd, Lq), 1)
    j = jnp.where((j >= 0) & (j < Lr), j, Lr)         # Lr -> the PAD column
    rp = jnp.concatenate([rs, jnp.full((B, 1), PAD, rs.dtype)], axis=1)
    rsk = jnp.transpose(jnp.take(rp, j, axis=1), (1, 0, 2))  # (nd, B, Lq)
    table = jnp.asarray(table)
    q = qs.astype(jnp.int32)
    out = jnp.full(rsk.shape, SENT8, jnp.int8)
    for a in range(ALPHABET_SIZE):
        out = jnp.where(rsk == a, table[a][q][None], out)
    return out


@functools.partial(jax.jit, static_argnames=(
    "gap_mode", "gap_open", "gap_extend", "bb", "interpret"))
def wave_scores_kernel(qs, rs, *, gap_mode: str = "linear",
                       gap_open: int | None = None,
                       gap_extend: int | None = None,
                       bb: int = DEFAULT_BB,
                       interpret: bool | None = None):
    """(B, Lq) x (B, Lr) int8 pair block -> (B, 1) int32 best local scores
    via the wavefront kernel. ``gap_mode="linear"`` (default gap = GAP) is
    bit-exact with `sw_scores_kernel`; ``"affine"`` scores Gotoh gaps
    (defaults -11/-1), bit-exact with `kernels.ref.sw_affine_ref`.

    B % bb == 0 is handled by padding in ops.wavefront_scores.
    """
    if gap_mode == "affine":
        go = GAP_OPEN if gap_open is None else int(gap_open)
        ge = GAP_EXTEND if gap_extend is None else int(gap_extend)
    else:
        go = GAP if gap_open is None else int(gap_open)
        ge = go
    return _wave_call(_skewed(qs, rs), mode=gap_mode, bb=bb,
                      interpret=interpret, gap_open=go, gap_extend=ge)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def wave_pid_kernel(qs, rs, *, bb: int = DEFAULT_BB,
                    interpret: bool | None = None):
    """(B, Lq) x (B, Lr) int8 pair block -> (B, 3) int32: the best linear
    gap local score, and the identities and length of the alignment that
    `align.smith_waterman._traceback_pid` walks back from the first best
    cell in row-major order, bit for bit.

    Every cell carries the (identities, length) of the walk that would
    start there, chosen by the walk's own rule: (0, 0) where H is 0, else
    its predecessor's plus one step: diagonal if H = H[i-1,j-1] + s, else
    up if H = H[i-1,j] + gap, else left; a diagonal step adds the match
    bit. The predecessors lie on the two previous anti-diagonals, so the
    walk is tracked forward and the DP matrix is never formed. Each lane
    keeps its first best cell (strict ``>`` as j grows); the first lane at
    the maximum wins, as ``np.argmax`` picks. B % bb == 0 is handled by
    padding in ops.wavefront_pid."""
    # ident << 16 | length stays a positive int32 while length < 2**15
    assert qs.shape[1] + rs.shape[1] <= 1 << 15, "pair block too long"
    best, walk = _wave_call(_skewed(qs, rs, _PID_TABLE), mode="pid", bb=bb,
                            interpret=interpret, gap_open=GAP)
    return jnp.concatenate([best, walk >> 16, walk & 0xFFFF], axis=1)


@functools.partial(jax.jit, static_argnames=("x", "bb", "interpret"))
def ungapped_scores_kernel(qs, rs, *, x: int | None, bb: int = DEFAULT_BB,
                           interpret: bool | None = None):
    """(B, Lq) x (B, Lr) int8 pair block -> (B, 1) int32 best ungapped
    X-drop run scores (``x=None``: no drop test, the best ungapped
    segment); bit-exact with `align.smith_waterman.ungapped_xdrop_scores`."""
    return _wave_call(_skewed(qs, rs), mode="ungapped", bb=bb,
                      interpret=interpret, x=x)


def _sw_kernel(qsub_ref, r_ref, out_ref, *, Lq: int):
    """Row wave: scan query rows with `fori_loop`, keeping only the
    previous DP row (bb, Lr+1) and the running best. Row i's substitution
    slice arrives PAD-masked as ``qsub_ref[i]`` (bb, A+1), so the row
    needs neither the query residue nor a dynamic slice; the within-row
    gap dependency is the max-plus prefix scan H = cummax(A + c*t) - c*t,
    as a log-doubling shifted max."""
    r = r_ref[...].astype(jnp.int32)          # (bb, Lr)
    bb, Lr = r.shape
    c = jnp.int32(-GAP)
    # iota, not arange: pallas kernels may not capture constant arrays
    t = jax.lax.broadcasted_iota(jnp.int32, (1, Lr), 1) + 1  # (1, Lr)

    def row_step(i, carry):
        prev, best = carry                    # (bb, Lr+1), (bb, 1)
        si = qsub_ref[i]                      # (bb, A+1) int32
        # sub_row[b, j] = B[q[b, i], r[b, j]] via 21 selects (no gathers)
        sub_row = jnp.zeros((bb, Lr), jnp.int32)
        for a in range(ALPHABET_SIZE + 1):
            sub_row = jnp.where(r == a, si[:, a:a + 1], sub_row)
        a_row = jnp.maximum(0, jnp.maximum(prev[:, :-1] + sub_row,
                                           prev[:, 1:] + GAP))
        x = a_row + c * t
        s = 1
        while s < Lr:
            shifted = jnp.concatenate(
                [jnp.full((bb, s), jnp.int32(-2**31 + 1)), x[:, :-s]], axis=1)
            x = jnp.maximum(x, shifted)
            s *= 2
        row_tail = x - c * t
        row = jnp.concatenate([jnp.zeros((bb, 1), jnp.int32), row_tail],
                              axis=1)
        best = jnp.maximum(best, jnp.max(row, axis=1, keepdims=True))
        return row, best

    prev0 = jnp.zeros((bb, Lr + 1), jnp.int32)
    best0 = jnp.zeros((bb, 1), jnp.int32)
    _, best = jax.lax.fori_loop(0, Lq, row_step, (prev0, best0))
    out_ref[...] = best


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def sw_scores_kernel(qs, rs, *, bb: int = DEFAULT_BB,
                     interpret: bool | None = None):
    """(B, Lq) x (B, Lr) int8 pair block -> (B, 1) int32 best local scores
    via the row wave. B % bb == 0 is handled by padding in
    ops.sw_wave_scores. ``interpret=None`` autodetects (native lowering on
    TPU)."""
    B, Lq = qs.shape
    Lr = rs.shape[1]
    assert B % bb == 0, "pad the pair block to a bb multiple"
    q = qs.astype(jnp.int32)
    masked = (q == PAD)[..., None] | (
        jnp.arange(ALPHABET_SIZE + 1) == PAD)
    qsub = jnp.where(masked, NEG, jnp.asarray(BLOSUM62_PADDED)[q])
    qsub = jnp.transpose(qsub, (1, 0, 2))     # (Lq, B, A+1) int32
    return pl.pallas_call(
        functools.partial(_sw_kernel, Lq=Lq),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((Lq, bb, ALPHABET_SIZE + 1), lambda i: (0, i, 0)),
            pl.BlockSpec((bb, Lr), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(qsub, rs)
