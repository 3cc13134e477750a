"""End-to-end ScalLoPS pipeline: the paper's two MapReduce jobs as one API.

    cfg = LSHConfig(k=4, T=22, f=32, d=0)
    sl = ScalLoPS(cfg)
    ref_sigs = sl.signatures(ref_ids_padded, ref_lengths)      # job 1 (refs)
    qry_sigs = sl.signatures(qry_ids_padded, qry_lengths)      # job 1 (queries)
    pairs, count, overflowed = sl.search(qry_sigs, ref_sigs)   # job 2

Reference signatures are reusable across query sets (paper §5.3: the
database-preparation analogue is paid once); `repro.index` builds that reuse
into a persistent, servable artifact.

`search` returns a SearchResult: the fixed-capacity pair buffer, the *true*
match count, and an `overflowed` flag — True when count exceeded the buffer
and rows were truncated, so callers can grow capacity and retry instead of
silently losing pairs (DESIGN.md §5 "no silent caps").
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import simhash
from .join import band_join, flip_join
from .hamming import threshold_pairs

SIG_BLOCK = 4096    # rows per signature-generation block


def _row_blocks(fn, ids, lengths, block: int = SIG_BLOCK):
    """``fn(ids, lengths)`` over row blocks of at most ``block`` rows, as
    one ``lax.map`` program. Both jobs-1 passes are per row, so blocking
    changes no result; it bounds the working set at one block's
    (rows, shingles, f) contributions — a 454,401-row Swiss-Prot-sized
    reference set in one piece asks a TPU for ~190 GB."""
    n = ids.shape[0]
    if n <= block:
        return fn(ids, lengths)
    nb = -(-n // block)
    pad = nb * block - n
    ids = jnp.pad(ids, ((0, pad), (0, 0)))
    lengths = jnp.pad(lengths, (0, pad))        # length 0: no shingles
    out = jax.lax.map(lambda a: fn(*a), (ids.reshape(nb, block, -1),
                                         lengths.reshape(nb, block)))
    return out.reshape(nb * block, *out.shape[2:])[:n]


@dataclass(frozen=True)
class LSHConfig:
    """Paper parameters (§5): shingle length k, neighbour threshold T,
    signature bits f, Hamming threshold d. Paper defaults k=3/T=13 for the
    perf runs and best quality at k=4/T=22/d=0; f was 32 (JVM int)."""
    k: int = 3
    T: int = 13
    f: int = 32
    d: int = 0
    scheme: str = "java"          # "java" (faithful) | "splitmix" (beyond-paper)
    siggen_method: str = "table"  # "table" (beyond-paper) | "matmul" (paper structure)
    join_method: str = "flip"     # "flip" (paper) | "band" | "dense"
    max_pairs: int = 1 << 16

    def __post_init__(self):
        assert self.f % 32 == 0 and self.f >= 32
        if self.scheme == "java":
            assert self.f <= 32, "java hashCode yields 32 bits (paper); use splitmix"


class SearchResult(NamedTuple):
    """Fixed-capacity join result. ``count`` is the true number of matches;
    ``overflowed`` is True iff the buffer truncated rows (grow + retry)."""
    pairs: jax.Array        # (max_pairs, >=2) int32, -1 past the stored rows
    count: jax.Array        # () int32 — true match count
    overflowed: jax.Array   # () bool — buffer truncated


class ScalLoPS:
    def __init__(self, cfg: LSHConfig):
        self.cfg = cfg
        self._sig_fn = jax.jit(functools.partial(
            _row_blocks, functools.partial(
                simhash.signatures, k=cfg.k, T=cfg.T, f=cfg.f,
                scheme=cfg.scheme, method=cfg.siggen_method)))
        self._count_fn = jax.jit(functools.partial(
            _row_blocks, functools.partial(
                simhash.feature_counts, k=cfg.k, T=cfg.T)))

    # ---- job 1: Signature Generator (map-only) ----
    def signatures(self, ids, lengths):
        return self._sig_fn(jnp.asarray(ids), jnp.asarray(lengths))

    def feature_counts(self, ids, lengths):
        """Per-sequence neighbour-feature counts (0 => degenerate
        all-ones signature; the paper filters those, §5.2)."""
        return self._count_fn(jnp.asarray(ids), jnp.asarray(lengths))

    # ---- job 2: Signature Processor ----
    def search(self, q_sigs, r_sigs, *, max_pairs: int | None = None,
               q_valid=None, r_valid=None) -> SearchResult:
        """Join the signature sets. q_valid/r_valid: optional bool masks —
        pairs touching invalid (zero-feature) sequences are dropped, per the
        paper's non-zero-signature rule. Returns a :class:`SearchResult`;
        check ``overflowed`` before trusting the pair buffer to be complete.
        """
        cfg = self.cfg
        mp = max_pairs or cfg.max_pairs
        truncated = jnp.zeros((), bool)
        if cfg.join_method == "flip":
            pairs, count = flip_join(q_sigs, r_sigs, f=cfg.f, d=cfg.d,
                                     max_pairs=mp)
        elif cfg.join_method == "band":
            # band_join's count is computed from a capacity-bounded candidate
            # buffer, so it can undercount once a band overran capacity; the
            # truncated flag covers that case.
            pairs, count, truncated = band_join(q_sigs, r_sigs, f=cfg.f,
                                                d=cfg.d, max_pairs=mp)
        elif cfg.join_method == "dense":
            pairs, count = threshold_pairs(q_sigs, r_sigs, cfg.d, mp)
        else:
            raise ValueError(cfg.join_method)
        # Overflow is judged on the raw join count: once the buffer
        # truncates, any downstream count (including the masked one below)
        # undercounts.
        overflowed = (count > mp) | truncated
        if q_valid is not None or r_valid is not None:
            qv = (jnp.asarray(q_valid) if q_valid is not None
                  else jnp.ones(q_sigs.shape[0], bool))
            rv = (jnp.asarray(r_valid) if r_valid is not None
                  else jnp.ones(r_sigs.shape[0], bool))
            ok = (pairs[:, 0] >= 0) \
                & qv[jnp.maximum(pairs[:, 0], 0)] \
                & rv[jnp.maximum(pairs[:, 1], 0)]
            pairs = jnp.where(ok[:, None], pairs, -1)
            count = jnp.sum(ok.astype(jnp.int32))
        return SearchResult(pairs, count, overflowed)
