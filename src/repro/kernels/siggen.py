"""Pallas TPU kernel: fused SimHash signature accumulation.

The Signature Generator's hot loop (paper §3.1 / Algorithm 2), restated as
two chained matmuls per tile (DESIGN.md §2) and fused so the (S, W)
neighbour-score matrix never leaves VMEM:

    grid (S/bs, W/bw):
        scores = rows_tile (bs, D) @ codebook_tile^T (D, bw)     # MXU
        wts    = where(scores >= T, scores, 0)                   # VPU
        V_tile += wts (bs, bw) @ H_tile (bw, f)                  # MXU

* rows: per-shingle BLOSUM row concatenations, D = k*(A+1) (A=20).
* codebook: one-hot words — static operand, streamed block-by-block.
* H: ±1 hyperplane matrix — static operand, streamed with the codebook.
* V: (S, f) int32 accumulator; the word-grid axis revisits the output block.

The sign/packing epilogue stays outside the kernel (cheap, O(S*f) bits).
VMEM per step ≈ bs*D + bw*D + bs*bw + bw*f + bs*f ints; with bs=bw=256,
D=105 (k=4), f=128: ~0.6 MB — far under the ~16 MB v5e VMEM budget, leaving
room for double-buffered streaming of the (W-major) codebook/H operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BS = 256   # shingle-block (sublane-aligned)
DEFAULT_BW = 512   # word-block (lane-aligned)


def _siggen_kernel(rows_ref, cb_ref, h_ref, v_ref, *, T: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        v_ref[...] = jnp.zeros_like(v_ref)

    rows = rows_ref[...].astype(jnp.int32)          # (bs, D)
    cb = cb_ref[...].astype(jnp.int32)              # (bw, D)
    scores = jax.lax.dot_general(
        rows, cb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)            # (bs, bw)
    wts = jnp.where(scores >= T, scores, 0)
    h = h_ref[...].astype(jnp.int32)                # (bw, f)
    v_ref[...] += jax.lax.dot_general(
        wts, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)            # (bs, f)


@functools.partial(jax.jit,
                   static_argnames=("T", "bs", "bw", "interpret"))
def siggen_accumulate_kernel(rows, cb, H, *, T: int, bs: int = DEFAULT_BS,
                             bw: int = DEFAULT_BW, interpret: bool = True):
    """Accumulate SimHash vectors V = Σ_w [score>=T]·score·H over the codebook.

    Args:
      rows: (S, D) int32 — shingle BLOSUM rows (padded shingles = all-zero
        rows, which score 0 < T against every word and contribute nothing).
      cb:   (W, D) int8  — one-hot codebook.
      H:    (W, f) int8  — ±1 hyperplanes.
    Returns:
      V: (S, f) int32 (callers apply sign + pack_bits).

    Does not compile for TPU: Mosaic has no int32 matmul (no search path
    calls it; signatures use the ``table`` method of `core.simhash`).
    """
    S, D = rows.shape
    W, f = H.shape
    assert S % bs == 0 and W % bw == 0, "pad in ops.signatures_fused"
    grid = (S // bs, W // bw)
    return pl.pallas_call(
        functools.partial(_siggen_kernel, T=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bw, D), lambda i, j: (j, 0)),
            pl.BlockSpec((bw, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bs, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, f), jnp.int32),
        interpret=interpret,
    )(rows, cb, H)
