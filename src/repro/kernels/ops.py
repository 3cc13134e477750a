"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples and platform dispatch: on TPU the
compiled kernels run natively; elsewhere (the CPU) they execute under
``interpret=True`` — same kernel body, Python evaluation — or fall back to
the jnp reference for speed when ``prefer_ref=True``.

The one shape-dependent route is candidate emission
(:func:`emission_route`): the upper-mask kernel holds a (U+1, E)
bucket-ownership compare and an (SB, E) one-hot in VMEM, so a slab past
``EMIT_KERNEL_MAX_CELLS`` (the flip layout at d >= 1 on a few thousand
sequences) takes the jnp product on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alphabet import PAD
from . import ref as kref
from .hamming import hamming_count_kernel, hamming_dist_kernel
from .siggen import siggen_accumulate_kernel
from .spgemm import DEFAULT_SLOT_BLOCK
from .sw import (on_tpu, resolve_interpret, sw_scores_kernel,
                 ungapped_scores_kernel, wave_pid_kernel, wave_scores_kernel)

# Largest max(U+1, slot block) x E working set the emission kernel is
# given. Compiled for v5e: 512 x 32768 compiles in ~20 s, 512 x 131072
# runs out of VMEM, and the compile time grows with the product.
EMIT_KERNEL_MAX_CELLS = 1 << 24


def _pad_rows(x, mult, value=0):
    n = x.shape[0]
    p = (-n) % mult
    if p == 0:
        return x, n
    return jnp.pad(x, ((0, p),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value), n


def all_pairs_hamming(q, r, *, bq: int = 256, br: int = 256,
                      prefer_ref: bool = False) -> jnp.ndarray:
    """All-pairs Hamming distances via the Pallas kernel (padded + cropped)."""
    if prefer_ref:
        return kref.hamming_dist_ref(q, r)
    qp, Q = _pad_rows(q, bq)
    rp, R = _pad_rows(r, br)
    out = hamming_dist_kernel(qp, rp, bq=bq, br=br,
                              interpret=resolve_interpret(None))
    return out[:Q, :R]


def hamming_counts(q, r, d: int, *, bq: int = 256, br: int = 256,
                   prefer_ref: bool = False) -> jnp.ndarray:
    """Per-query counts of references within Hamming distance d: (Q,) int32.

    Padded reference rows are all-ones signatures; queries are real data, so
    a padded ref can only collide if a real query is within d of the all-ones
    word — excluded by padding refs with the complement of 0 (distance from
    any real signature >= f - d in practice). To be exact we subtract the
    padded-row hits computed against the padding pattern.
    """
    if prefer_ref:
        return kref.hamming_count_ref(q, r, d)[:, 0]
    qp, Q = _pad_rows(q, bq)
    PADV = jnp.uint32(0xFFFFFFFF)
    rp, R = _pad_rows(r, br, value=PADV)
    out = hamming_count_kernel(qp, rp, d=d, bq=bq, br=br,
                               interpret=resolve_interpret(None))[:, 0]
    if rp.shape[0] != R:
        # exact correction: count hits of each query against the pad pattern
        pad_sig = jnp.full((1, r.shape[1]), PADV, jnp.uint32)
        per_pad = kref.hamming_count_ref(qp, pad_sig, d)[:, 0]
        out = out - per_pad * (rp.shape[0] - R)
    return out[:Q]


def sw_wave_scores(qs, rs, *, bb: int = 8, prefer_ref: bool = False,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Batched Smith-Waterman best scores for a (B, Lq) x (B, Lr) pair block
    via the Pallas row-wave kernel (padded + cropped); bit-exact with the
    jnp wave (`align.smith_waterman.sw_align_batch`), which is also the
    ``prefer_ref`` fallback. ``interpret=None`` autodetects by backend."""
    if prefer_ref:
        from ..align.smith_waterman import _sw_scores_batch
        return _sw_scores_batch(jnp.asarray(qs), jnp.asarray(rs))
    qp, B = _pad_rows(jnp.asarray(qs), bb, value=PAD)
    rp, _ = _pad_rows(jnp.asarray(rs), bb, value=PAD)
    out = sw_scores_kernel(qp, rp, bb=bb, interpret=resolve_interpret(interpret))
    return out[:B, 0]


def wavefront_scores(qs, rs, *, gap_mode: str = "linear",
                     gap_open: int | None = None,
                     gap_extend: int | None = None, bb: int = 8,
                     prefer_ref: bool = False,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Batched SW best scores for a (B, Lq) x (B, Lr) pair block via the
    anti-diagonal wavefront kernel (padded + cropped), linear or affine
    (Gotoh) gaps; score-exact with the row wave under ``"linear"`` and
    with `kernels.ref.sw_affine_ref` under ``"affine"``. The jnp sweep
    (`align.gotoh`) is the ``prefer_ref`` fallback (also the fast path
    off-TPU). ``interpret=None`` autodetects by backend."""
    if prefer_ref:
        from ..align import gotoh
        if gap_mode == "affine":
            return gotoh.sw_wave_affine(
                qs, rs,
                gap_open=gotoh.GAP_OPEN if gap_open is None else gap_open,
                gap_extend=(gotoh.GAP_EXTEND if gap_extend is None
                            else gap_extend))
        from ..align.smith_waterman import GAP
        return gotoh.sw_wave_linear(
            qs, rs, gap=GAP if gap_open is None else gap_open)
    qp, B = _pad_rows(jnp.asarray(qs), bb, value=PAD)
    rp, _ = _pad_rows(jnp.asarray(rs), bb, value=PAD)
    out = wave_scores_kernel(qp, rp, gap_mode=gap_mode, gap_open=gap_open,
                             gap_extend=gap_extend, bb=bb,
                             interpret=resolve_interpret(interpret))
    return out[:B, 0]


def wavefront_pid(qs, rs, *, bb: int = 8,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Batched SW score, identities and alignment length for a (B, Lq) x
    (B, Lr) pair block via the wavefront kernel's PID mode (padded +
    cropped) -> (B, 3) int32, on device. Bit-exact with
    `align.smith_waterman.sw_wave_pid` (the jnp row wave and host walk,
    which the all-pairs scheduler keeps off-TPU): PID is
    ``100 * ident / max(length, 1)``. Linear gap GAP, as the walk's rule
    reads it."""
    qp, B = _pad_rows(jnp.asarray(qs), bb, value=PAD)
    rp, _ = _pad_rows(jnp.asarray(rs), bb, value=PAD)
    out = wave_pid_kernel(qp, rp, bb=bb,
                          interpret=resolve_interpret(interpret))
    return out[:B]


def ungapped_wave_scores(qs, rs, *, x: int | None = 20, bb: int = 8,
                         prefer_ref: bool = False,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Batched ungapped X-drop prefilter scores for a (B, Lq) x (B, Lr) pair
    block via the Pallas diagonal-scan kernel (padded + cropped); bit-exact
    with `align.smith_waterman.ungapped_xdrop_scores` (the ``prefer_ref``
    fallback, which is also faster off-TPU). ``x=None`` drops the X-drop
    test: the best ungapped segment."""
    if prefer_ref:
        from ..align.smith_waterman import ungapped_xdrop_scores
        return ungapped_xdrop_scores(qs, rs, x=x)
    qp, B = _pad_rows(jnp.asarray(qs), bb, value=PAD)
    rp, _ = _pad_rows(jnp.asarray(rs), bb, value=PAD)
    out = ungapped_scores_kernel(qp, rp, x=x, bb=bb,
                                 interpret=resolve_interpret(interpret))
    return out[:B, 0]


def emission_route(n_offsets: int, n_entries: int, cap: int) -> str:
    """``"pallas"`` or ``"jnp"``: how :func:`emit_upper_pairs` emits a
    band slab of ``n_offsets`` (U+1) offsets and ``n_entries`` ids on this
    backend. The kernel runs natively on TPU within
    ``EMIT_KERNEL_MAX_CELLS``; the jnp product covers everything else."""
    sb = min(cap, DEFAULT_SLOT_BLOCK)
    fits = max(n_offsets, sb) * n_entries <= EMIT_KERNEL_MAX_CELLS
    return "pallas" if on_tpu() and fits else "jnp"


def emit_upper_pairs(offs_s, ids_s, *, cap: int,
                     prefer_ref: bool | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Band-stacked upper-mask SpGEMM candidate emission: offsets (G, U+1),
    ids (G, E) -> (G, cap, 2) int32 pair buffers (-1 past each band's true
    count) — the strict upper triangle of each band's AᵀA incidence
    product. ``prefer_ref=None`` takes :func:`emission_route`: the Pallas
    kernel (`kernels/spgemm.py`) on TPU for slabs that fit it, the jnp
    product of `repro.index.spgemm` otherwise. Bit-exact across all three
    paths (same pairs, same slot order)."""
    if prefer_ref is None:
        prefer_ref = emission_route(offs_s.shape[1], ids_s.shape[1],
                                    cap) == "jnp"
    if prefer_ref:
        from ..index.spgemm import masked_pair_product
        return jax.vmap(
            lambda o, i: masked_pair_product(o, i, cap=cap))(offs_s, ids_s)
    from .spgemm import upper_pairs_kernel
    return upper_pairs_kernel(offs_s, ids_s, cap=cap,
                              interpret=resolve_interpret(interpret))


def signatures_fused(rows, cb, H, *, T: int, bs: int = 256, bw: int = 512,
                     prefer_ref: bool = False) -> jnp.ndarray:
    """Fused SimHash accumulation V (S, f); pad shingle rows with zeros
    (score 0 < T contributes nothing) and codebook words with zeros (one-hot
    all-zero scores 0 < T, also inert) — exactness preserved for T >= 1."""
    assert T >= 1, "padding exactness requires T >= 1 (paper uses T >= 11)"
    if prefer_ref:
        return kref.siggen_accumulate_ref(rows, cb, H, T)
    rp, S = _pad_rows(rows, bs)
    cbp, W = _pad_rows(cb, bw)
    Hp, _ = _pad_rows(H, bw)
    out = siggen_accumulate_kernel(rp, cbp, Hp, T=T, bs=bs, bw=bw,
                                   interpret=resolve_interpret(None))
    return out[:S]
