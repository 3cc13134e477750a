"""Pallas TPU kernel: blocked all-pairs Hamming distance on packed signatures.

The Signature Processor's hot loop (paper §4.2). Signatures are packed
(N, nwords) uint32; the distance of a (query, reference) pair is
popcount(xor) summed over words. A (Q, R) sweep is a 2-D grid of VMEM tiles:

    grid (Q/bq, R/br):
        dist[bq, br] = sum_w popcount(q_tile[:, None, w] ^ r_tile[None, :, w])

XOR + ``lax.population_count`` run on the VPU; tiles are MXU/VPU-aligned
(bq, br multiples of 8x128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BQ = 256
DEFAULT_BR = 256


def _dist_kernel(q_ref, r_ref, out_ref):
    q = q_ref[...]                      # (bq, nw) uint32
    r = r_ref[...]                      # (br, nw) uint32
    x = q[:, None, :] ^ r[None, :, :]   # (bq, br, nw)
    out_ref[...] = jnp.sum(
        jax.lax.population_count(x).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("bq", "br", "interpret"))
def hamming_dist_kernel(q, r, *, bq: int = DEFAULT_BQ, br: int = DEFAULT_BR,
                        interpret: bool = True):
    """(Q, nw) x (R, nw) uint32 -> (Q, R) int32 distances. Q % bq == R % br == 0
    is handled by padding inside ops.all_pairs_hamming."""
    Q, nw = q.shape
    R = r.shape[0]
    assert Q % bq == 0 and R % br == 0, "pad inputs to block multiples"
    grid = (Q // bq, R // br)
    return pl.pallas_call(
        _dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, nw), lambda i, j: (i, 0)),
            pl.BlockSpec((br, nw), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, br), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, R), jnp.int32),
        interpret=interpret,
    )(q, r)
