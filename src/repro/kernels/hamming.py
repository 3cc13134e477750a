"""Pallas TPU kernel: blocked all-pairs Hamming distance on packed signatures.

The Signature Processor's hot loop (paper §4.2). Signatures are packed
(N, nwords) uint32; the distance of a (query, reference) pair is
popcount(xor) summed over words. A (Q, R) sweep is a 2-D grid of VMEM tiles:

    grid (Q/bq, R/br):
        dist[bq, br] = sum_w popcount(q_tile[:, None, w] ^ r_tile[None, :, w])

XOR + ``lax.population_count`` run on the VPU; tiles are MXU/VPU-aligned
(bq, br multiples of 8x128). A second kernel fuses the ``<= d`` threshold and
reduces to per-query match counts, accumulated across the reference grid axis
(revisited output block) — the roofline-friendly form when only counts or a
candidate mask are needed, as in the join's verification pass.
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


def _count_kernel(q_ref, r_ref, out_ref, *, d: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[...]
    r = r_ref[...]
    x = q[:, None, :] ^ r[None, :, :]
    dist = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    hits = (dist <= d).astype(jnp.int32)                # (bq, br)
    out_ref[...] += jnp.sum(hits, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("d", "bq", "br", "interpret"))
def hamming_count_kernel(q, r, *, d: int, bq: int = DEFAULT_BQ,
                         br: int = DEFAULT_BR, interpret: bool = True):
    """Fused threshold+reduce: per-query count of references within distance d.

    (Q, nw) x (R, nw) -> (Q, 1) int32. The reference grid axis revisits the
    output block and accumulates (classic Pallas reduction pattern). d is a
    compile-time constant (the paper sweeps d in {0,1,2}).

    Does not compile for TPU at the default blocks: the (bq, br, nw)
    XOR block overflows the 16 MiB scoped VMEM (no search path calls it).
    """
    Q, nw = q.shape
    R = r.shape[0]
    assert Q % bq == 0 and R % br == 0
    grid = (Q // bq, R // br)
    return pl.pallas_call(
        functools.partial(_count_kernel, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, nw), lambda i, j: (i, 0)),
            pl.BlockSpec((br, nw), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        interpret=interpret,
    )(q, r)
