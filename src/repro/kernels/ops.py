"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples and platform dispatch: on TPU the
compiled kernels run natively; elsewhere (the CPU) they execute under
``interpret=True`` — same kernel body, Python evaluation. Whether a path
takes a kernel or its jnp sweep is the caller's choice, made once
(`repro.allpairs.tiles` keys it on the backend).

The one shape-dependent route is candidate emission
(:func:`emission_route`): the upper-mask kernel holds a (U+1, E)
bucket-ownership compare and an (SB, E) one-hot in VMEM, so a slab past
``EMIT_KERNEL_MAX_CELLS`` (the flip layout at d >= 1 on a few thousand
sequences) takes the jnp product on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.alphabet import PAD
from .hamming import hamming_dist_kernel
from .spgemm import DEFAULT_SLOT_BLOCK
from .sw import (on_tpu, resolve_interpret, ungapped_scores_kernel,
                 wave_pid_kernel, wave_scores_kernel)

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


def all_pairs_hamming(q, r, *, bq: int = 256, br: int = 256) -> jnp.ndarray:
    """All-pairs Hamming distances via the Pallas kernel (padded + cropped)."""
    qp, Q = _pad_rows(q, bq)
    rp, R = _pad_rows(r, br)
    out = hamming_dist_kernel(qp, rp, bq=bq, br=br,
                              interpret=resolve_interpret(None))
    return out[:Q, :R]


def wavefront_scores(qs, rs, *, gap_mode: str = "linear",
                     gap_open: int | None = None,
                     gap_extend: int | None = None, bb: int = 8,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Batched SW best scores for a (B, Lq) x (B, Lr) pair block via the
    anti-diagonal wavefront kernel (padded + cropped), linear or affine
    (Gotoh) gaps; score-exact with the jnp sweep of `align.gotoh` and with
    `kernels.ref.sw_affine_ref`. ``interpret=None`` autodetects by
    backend."""
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
                         interpret: bool | None = None) -> jnp.ndarray:
    """Batched ungapped X-drop prefilter scores for a (B, Lq) x (B, Lr) pair
    block via the Pallas diagonal-scan kernel (padded + cropped); bit-exact
    with `align.smith_waterman.ungapped_xdrop_scores`. ``x=None`` drops
    the X-drop test: the best ungapped segment."""
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


def emit_upper_pairs(offs_s, ids_s, *, cap: int) -> jnp.ndarray:
    """Band-stacked upper-mask SpGEMM candidate emission: offsets (G, U+1),
    ids (G, E) -> (G, cap, 2) int32 pair buffers (-1 past each band's true
    count) — the strict upper triangle of each band's AᵀA incidence
    product, routed by :func:`emission_route`: the Pallas kernel
    (`kernels/spgemm.py`) on TPU for slabs that fit it, the jnp product of
    `repro.index.spgemm` otherwise. Bit-exact across both (same pairs,
    same slot order)."""
    if emission_route(offs_s.shape[1], ids_s.shape[1], cap) == "jnp":
        from ..index.spgemm import masked_pair_product
        return jax.vmap(
            lambda o, i: masked_pair_product(o, i, cap=cap))(offs_s, ids_s)
    from .spgemm import upper_pairs_kernel
    return upper_pairs_kernel(offs_s, ids_s, cap=cap,
                              interpret=resolve_interpret(None))
