"""Pallas TPU kernels: batched Smith-Waterman over a pair block — the
anti-diagonal (wavefront) Gotoh sweep, its percent-identity mode, the
ungapped X-drop prefilter, and the legacy row wave.

The all-pairs tiler's inner loop (`repro.allpairs.tiles`): score a block of
(query, reference) pairs in one program. The wavefront and ungapped
kernels share one body over the skewed substitution rows of
`align.gotoh` (``sk[c, b, i] = s_b[i, c-i]``): lanes are query rows, so
every DP cell's predecessors sit on the two previous anti-diagonals and
one diagonal step is elementwise arithmetic over (bb, Lq) lanes — the
gapped step is the wavefront recurrence, the ungapped step keeps only the
diagonal move with BLAST's X-drop restart, and the PID step also carries
the identities and length of the walk back from each cell. The grid is
(pair block, diagonal block); the DP carries live in VMEM scratch across
the diagonal axis.

The kernel makes each diagonal's row itself, so only the (B, Lq) and
(B, Lr) residues cross HBM and no (nd, B, Lq) block is ever formed. At a
pair block's first diagonal block it packs each query lane's column of
the substitution table into six int32 words (the query profile, kept in
VMEM); every diagonal block brings ``DIAG_BLOCK`` reference residues. A
carried vector holds the reference residue of each query lane, r[c - i],
behind the residues still to enter: one lane rotate a diagonal moves
every residue one query row on, and the row is the profile word the
residue picks, shifted to its byte. `_skewed` builds the same rows in
XLA, the reference the tests sweep through `_wave_call`.

Everything is written in the subset Mosaic lowers: static lane rotates
plus a mask (``pltpu.roll``), lane-aligned slices and concatenates,
per-lane shifts and selects — no ``dynamic_slice`` of a loaded value and
no gather.

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
UNROLL = 4              # diagonal steps per loop iteration of the wave kernels


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


# The query profile: word k of query residue q packs table[4k + j, q] as
# the signed byte j (j < 4), so six int32 words cover the 20 residues and
# PAD (word 5, byte 0), whose row is SENT8 whatever the table says
_PROFILE_WORDS = 6


def _packed_profile(table) -> tuple[tuple[int, ...], ...]:
    """(A+1, A+1) int8 substitution table -> ``words[k][q]``, Python ints
    (a kernel may not capture a constant array)."""
    rows = np.full((4 * _PROFILE_WORDS, ALPHABET_SIZE + 1), SENT8, np.int64)
    rows[:ALPHABET_SIZE] = np.asarray(table, np.int64)[:ALPHABET_SIZE]
    b = (rows & 0xFF).reshape(_PROFILE_WORDS, 4, ALPHABET_SIZE + 1)
    words = sum(b[:, j] << (8 * j) for j in range(4)).astype(np.uint32)
    return tuple(map(tuple, words.view(np.int32).tolist()))


def _build_profile(q, words):
    """(bb, Lq) int32 query residues -> the ``len(words)`` profile words
    of each lane, by one compare per residue and one select per word."""
    out = [jnp.zeros(q.shape, jnp.int32) for _ in words]
    for a in range(ALPHABET_SIZE + 1):
        hit = q == a
        out = [jnp.where(hit, w[a], o) for w, o in zip(words, out)]
    return out


def _code(r):
    """Reference residue -> the code the kernel carries: 32 * its profile
    word + 8 * (3 - its byte), so ``>=`` picks the word and ``& 24`` is the
    left shift that brings the byte to the top. PAD -> word 5, byte 0."""
    return (r >> 2) * 32 + (3 - (r & 3)) * 8


def _lookup(prof, code):
    """The substitution row ``table[r_b[c-i], q_b[i]]`` (bb, Lq) int32
    from the query profile's words and each lane's reference residue
    `_code`: its word, then its byte, sign-extended."""
    w01 = jnp.where(code >= 32, prof[1], prof[0])
    w23 = jnp.where(code >= 96, prof[3], prof[2])
    w45 = jnp.where(code >= 160, prof[5], prof[4])
    w = jnp.where(code >= 128, w45, jnp.where(code >= 64, w23, w01))
    return (w << (code & 24)) >> 24


def _unrolled_loop(n: int, body, st):
    """``fori_loop(0, n, body, st)`` taking ``UNROLL`` steps an iteration
    (Mosaic lowers no ``unroll=``): the scheduler can then build the next
    diagonals' rows while a step's recurrence waits on its latency."""
    def steps(c, st):
        for j in range(UNROLL):
            st = body(c * UNROLL + j, st)
        return st
    st = jax.lax.fori_loop(0, n // UNROLL, steps, st)
    return jax.lax.fori_loop(n - n % UNROLL, n, body, st)


def _wave_kernel(*refs, mode: str, gap_open: int, gap_extend: int,
                 x: int | None, dc: int, words):
    """``dc`` diagonals of one (bb,) pair block. With ``words`` (the
    packed table) the inputs are the (bb, Lq) query residues (Lq whole
    vregs) and this block's ``DIAG_BLOCK`` reference residue codes fed in
    reverse (`_feed`), and the kernel makes each diagonal's substitution
    row itself; without, the input is a (dc, bb, Lq) slice of a prebuilt
    skewed block. Then come the outputs (the best score; in ``pid`` mode also the packed walk of
    the best cell), then the carries: ``carry[0]`` is the running best, the
    rest the mode's DP lanes, all zero at the first diagonal block and
    written back to VMEM scratch after each one; with ``words`` also the
    reference residue code at each lane and the query profile."""
    built = words is not None
    n_in = 2 if built else 1
    n_out = 2 if mode == "pid" else 1
    out_ref, carry = refs[n_in], refs[n_in + n_out:]
    if built:
        (q_ref, feed_ref), (rd_ref, prof_ref) = refs[:2], carry[-2:]
        carry = carry[:-2]
    else:
        sk_ref = refs[0]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for ref in carry:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)
        if built:       # diagonal -1: the reference lies wholly ahead
            rd_ref[...] = jnp.full(rd_ref.shape, _code(PAD), jnp.int32)
            q = q_ref[...].astype(jnp.int32)
            for k, w in enumerate(_build_profile(q, words)):
                prof_ref[k] = w

    lane = jax.lax.broadcasted_iota(jnp.int32, carry[0].shape, 1)
    lane0 = lane == 0

    def shift(v):
        return _shift_right(v, lane0)

    def cell(s, st):
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

    st = tuple(ref[...] for ref in carry)
    if built:
        # lanes [0, DIAG_BLOCK) hold the residue codes still to enter,
        # the rest diagonal c's, r[c - i] at query lane i: one lane
        # rotate moves every reference residue one query row on
        prof = [prof_ref[k] for k in range(len(words))]

        def step(c, st):
            xr = pltpu.roll(st[0], 1, 1)
            return (xr, *cell(_lookup(prof, xr[:, DIAG_BLOCK:]), st[1:]))

        xr = jnp.concatenate([feed_ref[...], rd_ref[...]], axis=1)
        xr, *st = _unrolled_loop(dc, step, (xr, *st))
        rd_ref[...] = xr[:, DIAG_BLOCK:]
    else:
        st = jax.lax.fori_loop(
            0, dc, lambda c, st: cell(sk_ref[c].astype(jnp.int32), st), st)
    for ref, v in zip(carry, st):
        ref[...] = v

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _emit():
        best = jnp.max(st[0], axis=1, keepdims=True)
        out_ref[...] = best
        if mode == "pid":       # the first lane (query row) at the best
            first = jnp.min(jnp.where(st[0] == best, lane, lane.shape[1]),
                            axis=1, keepdims=True)
            refs[n_in + 1][...] = jnp.max(
                jnp.where(lane == first, st[5], 0), axis=1, keepdims=True)


# XLA names each kernel's op after these (``ungapped_prefilter.1``), so a
# profile tells the kernels from the XLA work around them
_KERNEL_NAMES = {"linear": "wavefront_dp", "affine": "wavefront_dp",
                 "ungapped": "ungapped_prefilter", "pid": "wavefront_pid"}


def _sweep(inputs, in_specs, *, words, nd: int, B: int, Lq: int, mode: str,
           bb: int, interpret: bool | None, gap_open: int, gap_extend: int,
           x: int | None):
    """The wave kernel's pallas_call over a (B // bb, diagonal block)
    grid -> (B, 1) int32 best scores, and in ``pid`` mode also (B, 1)
    int32 packed walks."""
    if x is not None:       # past 11 * L no run can drop; keep it int32
        x = min(int(x), 1 << 30)
    dc = min(DIAG_BLOCK, nd)
    n_carry = {"affine": 5, "pid": 6}.get(mode, 3 if x is None else 5)
    scratch = [pltpu.VMEM((bb, Lq), jnp.int32)] * n_carry
    if words is not None:
        scratch += [pltpu.VMEM((bb, Lq), jnp.int32),
                    pltpu.VMEM((len(words), bb, Lq), jnp.int32)]
    out_spec = pl.BlockSpec((bb, 1), lambda i, k: (i, 0))
    out_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    if mode == "pid":
        out_spec, out_shape = [out_spec] * 2, [out_shape] * 2
    return pl.pallas_call(
        functools.partial(_wave_kernel, mode=mode, gap_open=gap_open,
                          gap_extend=gap_extend, x=x, dc=dc, words=words),
        grid=(B // bb, pl.cdiv(nd, dc)),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=_KERNEL_NAMES[mode],
    )(*inputs)


def _feed(rs, n_blocks: int):
    """(B, Lr) int8 -> (B, n_blocks * DIAG_BLOCK) int32 reference residue
    codes (`_code`): diagonal block k's, lane m holding the code of
    r[(k+1) * DIAG_BLOCK - 1 - m] (PAD past the end), so each diagonal's
    residue enters at the last lane the kernel rotates out of the block."""
    B, Lr = rs.shape
    n = n_blocks * DIAG_BLOCK
    rp = jnp.pad(rs.astype(jnp.int32), ((0, 0), (0, n - Lr)),
                 constant_values=PAD)
    rp = jnp.flip(rp.reshape(B, n_blocks, DIAG_BLOCK), axis=2)
    return _code(rp.reshape(B, n))


def _wave_rows(qs, rs, table, *, mode: str, bb: int, interpret: bool | None,
               gap_open: int = 0, gap_extend: int = 0,
               x: int | None = None):
    """Run the wave kernel over a (B, Lq) x (B, Lr) int8 pair block,
    making each anti-diagonal's substitution row ``table[r_b[c-i],
    q_b[i]]`` (SENT8 on PAD and outside the matrix, as `_skewed` builds
    it) in VMEM from the query profile and the reference residues. Only
    the (B, Lq) and (B, Lr) residues cross HBM; the diagonal axis pads to
    a ``dc`` multiple with diagonals past the matrix, which are all
    sentinel. Results as `_wave_call`."""
    B, Lq = qs.shape
    nd = Lq + rs.shape[1] - 1
    assert B % bb == 0, "pad the pair block to a bb multiple"
    n_blocks = pl.cdiv(nd, min(DIAG_BLOCK, nd))
    # PAD query rows up to whole vregs: they only ever hold sentinel cells,
    # and every lane rotate then moves whole vregs
    lanes = pl.cdiv(Lq, 128) * 128
    qs = jnp.pad(qs, ((0, 0), (0, lanes - Lq)), constant_values=PAD)
    return _sweep(
        (qs, _feed(rs, n_blocks)),
        [pl.BlockSpec((bb, lanes), lambda i, k: (i, 0)),
         pl.BlockSpec((bb, DIAG_BLOCK), lambda i, k: (i, k))],
        words=_packed_profile(table), nd=nd, B=B, Lq=lanes, mode=mode, bb=bb,
        interpret=interpret, gap_open=gap_open, gap_extend=gap_extend, x=x)


def _wave_call(sk, *, mode: str, bb: int, interpret: bool | None,
               gap_open: int = 0, gap_extend: int = 0,
               x: int | None = None):
    """Run the wave kernel over a prebuilt skewed (nd, B, Lq) int8 block
    (`_skewed`) -> (B, 1) int32 best scores, and in ``pid`` mode also
    (B, 1) int32 packed walks. The diagonal axis pads to a ``dc`` multiple
    with sentinel rows, which no score can come from (`align.gotoh`).
    The jitted entries make the rows in the kernel (`_wave_rows`); this
    route sweeps rows made elsewhere, which tests compare it with."""
    nd, B, Lq = sk.shape
    assert B % bb == 0, "pad the pair block to a bb multiple"
    dc = min(DIAG_BLOCK, nd)
    pad = (-nd) % dc
    if pad:
        sk = jnp.concatenate(
            [sk, jnp.full((pad, B, Lq), SENT8, jnp.int8)], axis=0)
    return _sweep(
        (sk,), [pl.BlockSpec((dc, bb, Lq), lambda i, k: (k, i, 0))],
        words=None, nd=nd, B=B, Lq=Lq, mode=mode, bb=bb,
        interpret=interpret, gap_open=gap_open, gap_extend=gap_extend, x=x)


# The PID kernel's substitution table: 2 * BLOSUM62 + (residues equal),
# so one int8 cell carries the score (``>> 1``) and the match bit (``& 1``)
# through the same rows; PAD stays SENT8 (-50 once shifted, still a
# sentinel: no path through it can tie a real best)
_PID_TABLE = np.where(_BSENT == SENT8, SENT8,
                      2 * _BSENT.astype(np.int32)
                      + np.eye(ALPHABET_SIZE + 1, dtype=np.int32)
                      ).astype(np.int8)


def _skewed(qs, rs, table=_BSENT):
    """(B, Lq) x (B, Lr) int8 -> the (nd, B, Lq) int8 skewed substitution
    block, ``sk[c, b, i] = table[r_b[c-i], q_b[i]]`` (SENT8 on PAD and
    outside the matrix): in XLA, the rows the wave kernels make for
    themselves in VMEM, which tests sweep through `_wave_call` to check
    them. The reference is skewed by one gather of column ``c - i`` and
    the query axis resolved by 20 selects."""
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
    return _wave_rows(qs, rs, _BSENT, mode=gap_mode, bb=bb,
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
    best, walk = _wave_rows(qs, rs, _PID_TABLE, mode="pid", bb=bb,
                            interpret=interpret, gap_open=GAP)
    return jnp.concatenate([best, walk >> 16, walk & 0xFFFF], axis=1)


@functools.partial(jax.jit, static_argnames=("x", "bb", "interpret"))
def ungapped_scores_kernel(qs, rs, *, x: int | None, bb: int = DEFAULT_BB,
                           interpret: bool | None = None):
    """(B, Lq) x (B, Lr) int8 pair block -> (B, 1) int32 best ungapped
    X-drop run scores (``x=None``: no drop test, the best ungapped
    segment); bit-exact with `align.smith_waterman.ungapped_xdrop_scores`."""
    return _wave_rows(qs, rs, _BSENT, mode="ungapped", bb=bb,
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
