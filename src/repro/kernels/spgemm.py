"""Pallas TPU kernel: batched masked-SpGEMM candidate emission.

The device inner loop of ``repro.index.spgemm``: each band's bucket CSR is
the sequence×bucket incidence matrix ``A``, and the strict upper triangle
of the Boolean-semiring ``AᵀA`` — every unordered within-bucket pair,
emitted once — is flattened into a fixed-capacity pair buffer. The grid is
2-D over (band slab, slot block); each program holds one band's offsets
(as a ``(U+1, 1)`` column) and entry ids (as a ``(1, E)`` row) in VMEM and
materializes one lane-dense ``(1, SB)`` block of output slots.

Everything is expressed in the Pallas-friendly subset the SW kernels
established (`kernels/sw.py`): ``broadcasted_iota`` instead of captured
``arange`` constants, searchsorted as a comparison-sum reduction, gathers
as one-hot compare-and-reduce, and the per-band prefix sum (slot -> owning
entry) as a log-doubling lane-rotate add (Hillis-Steele) — ``lax.cumsum``
does not lower inside Pallas TPU kernels. The working set is the
(U+1, E) bucket-membership comparison (once per band) and an (SB, E)
one-hot block, both quadratic in the slab, so `kernels.ops.emission_route`
sends only slabs within ``EMIT_KERNEL_MAX_CELLS`` here (a few thousand
entries per band; the flip layout at d >= 1 exceeds it).

``interpret`` defaults to autodetect (native lowering on TPU, interpret
elsewhere, i.e. on the CPU). Output is bit-exact with the jnp
reference ``repro.index.spgemm.masked_pair_product(mask="upper")`` and the
host oracle `kernels.ref.spgemm_upper_ref`: same pairs in the same slot
order ((lo, hi)-oriented, -1 past each band's true count).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sw import resolve_interpret

DEFAULT_SLOT_BLOCK = 512


def _row(col):
    """(SB, 1) column -> (1, SB) row: broadcast to a full lane tile and
    transpose, so each slot block stores lane-dense."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _upper_kernel(offs_ref, ids_ref, lo_ref, hi_ref, inc_ref, exc_ref, *,
                  SB: int):
    """Entries run along lanes (1, E); this block's slots along sublanes
    (SB, 1). The bucket offsets arrive as a column (U1, 1), so every
    comparison below is a broadcast of a row against a column — no
    gathers or dynamic slices. The per-entry prefix (``inc``/``exc``) is
    built once per band, at its first slot block, into VMEM scratch."""
    ids = ids_ref[...]                            # (1, E)
    E = ids.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _prefix():
        offs = offs_ref[...]                      # (U1, 1)
        U1 = offs.shape[0]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
        # owning bucket of each entry: searchsorted(offs, pos, 'right') - 1,
        # as a comparison-sum (slab padding repeats the last offset, so
        # padded entry positions resolve past the last real bucket and own
        # nothing)
        b = jnp.sum((offs <= pos).astype(jnp.int32), axis=0,
                    keepdims=True) - 1                            # (1, E)
        # bucket end of each entry: offs[b + 1] via one-hot reduce
        row = jax.lax.broadcasted_iota(jnp.int32, (U1, E), 0)
        bp1 = jnp.clip(b + 1, 0, U1 - 1)
        end = jnp.sum(jnp.where(row == bp1, offs, 0), axis=0,
                      keepdims=True)                              # (1, E)
        # upper mask: entry p pairs with the LATER members of its bucket
        cnt = jnp.maximum(end - 1 - pos, 0)                       # (1, E)
        # inclusive prefix sum over entries: log-doubling shifted add
        inc = cnt
        s = 1
        while s < E:
            inc = inc + jnp.where(pos < s, 0, pltpu.roll(inc, s, 1))
            s *= 2
        inc_ref[...] = inc
        exc_ref[...] = inc - cnt    # exclusive prefix = first slot of p

    inc = inc_ref[...]
    exc = exc_ref[...]
    total = jnp.max(inc)          # == inc[0, -1]: cumsum is non-decreasing
    # this block's global slot indices
    sl = (jax.lax.broadcasted_iota(jnp.int32, (SB, 1), 0)
          + pl.program_id(1) * SB)                                # (SB, 1)
    # owning entry of each slot: searchsorted(inc, slot, 'right')
    p = jnp.sum((inc <= sl).astype(jnp.int32), axis=1,
                keepdims=True)                                    # (SB, 1)
    p = jnp.clip(p, 0, E - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SB, E), 1)
    sel = lane == p                                               # one-hot
    a = jnp.sum(jnp.where(sel, ids, 0), axis=1, keepdims=True)   # left id
    exc_p = jnp.sum(jnp.where(sel, exc, 0), axis=1, keepdims=True)
    # upper-mask window starts at the NEXT entry: win_start[p] = p + 1
    j = jnp.clip(p + 1 + (sl - exc_p), 0, E - 1)
    partner = jnp.sum(jnp.where(lane == j, ids, 0), axis=1, keepdims=True)
    valid = sl < total
    lo_ref[...] = _row(jnp.where(valid, jnp.minimum(a, partner), -1))
    hi_ref[...] = _row(jnp.where(valid, jnp.maximum(a, partner), -1))


@functools.partial(jax.jit, static_argnames=("cap", "slot_block",
                                             "interpret"))
def upper_pairs_kernel(offs_s, ids_s, *, cap: int,
                       slot_block: int = DEFAULT_SLOT_BLOCK,
                       interpret: bool | None = None):
    """Band-stacked upper-mask SpGEMM emission: offsets (G, U+1) int32,
    ids (G, E) int32 -> (G, cap, 2) int32 pair buffers, -1 past each
    band's true count. ``cap`` must be a power of two (the emission caps
    of `allpairs/selfjoin.py` always are), so the slot grid divides
    evenly. Bit-exact with the jnp reference (same slot order)."""
    G, E = ids_s.shape
    U1 = offs_s.shape[1]
    SB = min(cap, slot_block)
    assert cap % SB == 0, "cap must be a pow2 multiple of the slot block"
    # the band axis is a squeezed block dim, so every block's last two
    # dims are full dims or (8, 128)-aligned: offsets as a (U1, 1) column,
    # ids as a (1, E) row, each slot block stored as a (1, SB) row
    lo, hi = pl.pallas_call(
        functools.partial(_upper_kernel, SB=SB),
        grid=(G, cap // SB),
        in_specs=[
            pl.BlockSpec((None, U1, 1), lambda g, s: (g, 0, 0)),
            pl.BlockSpec((None, 1, E), lambda g, s: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, SB), lambda g, s: (g, 0, s)),
            pl.BlockSpec((None, 1, SB), lambda g, s: (g, 0, s)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, 1, cap), jnp.int32),
            jax.ShapeDtypeStruct((G, 1, cap), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, E), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(offs_s.astype(jnp.int32)[:, :, None], ids_s.astype(jnp.int32)[:, None])
    return jnp.stack([lo[:, 0], hi[:, 0]], axis=-1)
