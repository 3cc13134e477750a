"""Pair scheduler: candidate pairs -> device-resident batched SW waves.

At corpus scale the candidate set of the self-join is far too ragged to
score naively: pair lengths vary, per-pair DP calls retrace the jit cache
for every new (Lq, Lr), and any host work between dispatches leaves the
device idle. The scheduler imposes structure — and keeps the whole hot path
on device:

1. **padded-shape waves** — pairs are keyed by their padded (Lq, Lr) on
   a quantized ladder (same idea as ``QueryEngine``'s padding ladder: a
   small, closed set of shapes keeps the jit cache stable), over the whole
   pair set, so each shape's bucket fills ``ceil(m / B)`` waves and only
   its last wave carries padding. The gather reads the corpus uploaded
   once (point 2), so pairs from any rows can share a wave.
2. **fused device gather** — the padded corpus ``(N, Lmax)`` is uploaded
   ONCE; each wave is one jitted take-and-mask program over pair index
   arrays (``ids[pair_idx, :Lq]``), so the only per-wave H2D traffic is the
   (B,) index vectors — no per-pair host copy loop
   (``device_gather=False`` restores the PR 2 host path, bit-exact).
3. **ungapped X-drop prefilter** (``prefilter=True``) — every wave first
   runs a cheap ungapped diagonal scan (BLAST-style X-drop extension, an
   elementwise DP with no within-row prefix scan); only pairs whose
   ungapped score reaches ``prefilter_min`` proceed to the full gapped
   wave. The ungapped score is a *lower bound* of the SW score, so the
   filter never adds pairs; rejected pairs report their ungapped score
   (``kept`` marks the survivors, whose scores are full SW, bit-exact).
4. **async double-buffered dispatch** — wave n+1's gather+DP is issued
   while wave n's scores are still in flight; a small FIFO ring
   (``inflight``) drains ``device_get`` results, so wall-clock tracks
   device DP time instead of Python dispatch.
5. **multi-device waves** (``n_devices > 1``) — each wave batch is split
   over the first ``n_devices`` of ``jax.devices()`` as ONE SPMD program
   (``shard_map``: pair index vectors partitioned, corpus replicated), so
   ``n_devices`` pair blocks gather+score concurrently per dispatch — the
   reduce-side join of the sharded self-join run on the reducers
   themselves. SPMD (not per-device round-robin dispatch) is load-bearing:
   the CPU PJRT client serializes independent per-device executions, and
   only partitions *inside* one program run on parallel threads; on
   accelerator meshes the same program overlaps the usual way. Pairs are
   embarrassingly parallel, so the split is bit-exact by construction.

    pairs ──wave_plan──▶ [gather ▶ prefilter ▶ full SW] ──▶ drain ring
                           (one jitted program per wave shape,
                            split P("wave") over n_devices)

Scores (and optionally PID: on TPU the wavefront kernel's PID mode, which
tracks each alignment's identities and length through the sweep; off-TPU
the row wave's DP matrices and the host traceback) come back aligned with
the input pair order.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..align.smith_waterman import (gather_rows, sw_gather_scores,
                                    sw_scores_device, sw_wave_pid,
                                    ungapped_xdrop_scores)
from ..core.alphabet import PAD
from ..kernels.sw import on_tpu
from ..obs import span, trace_sentinel


@dataclass(frozen=True)
class WaveConfig:
    wave_batch: int = 64         # pairs per full-SW wave (upper bound)
    len_quantum: int = 64        # pad pair lengths to multiples of this
    max_wave_cells: int = 1 << 23  # B*Lq*Lr budget; shrinks B for long pairs
    device_gather: bool = True   # fused on-device wave gather (False: PR 2
                                 # host copy loop, bit-exact, for comparison)
    inflight: int = 2            # async ring depth: waves in flight before
                                 # the oldest result is drained to host
    n_devices: int = 1           # split each wave over this many devices
                                 # as one SPMD shard_map program (clamped
                                 # to jax.device_count(); needs
                                 # device_gather; wave_batch becomes the
                                 # PER-DEVICE batch). Ignored by the
                                 # Pallas waves (PID ones included) and
                                 # the host PID route: both stay
                                 # single-device.
    dp_kernel: str = "wavefront"  # score-only DP sweep: "wavefront" (the
                                 # anti-diagonal Gotoh sweep of
                                 # `align.gotoh`, ~2.8x on CPU) or
                                 # "rowwave" (the int32 prefix-scan row
                                 # wave, linear-gap fallback). PID waves
                                 # take the wavefront kernel's PID mode
                                 # with use_pallas, else the row wave
                                 # (the host traceback reads its DP
                                 # matrices).
    gap_mode: str = "linear"     # gap model: "linear" (GAP = -4, both
                                 # kernels, scores bit-exact across them)
                                 # or "affine" (Gotoh open/extend,
                                 # wavefront only)
    gap_open: int | None = None  # None -> GAP (linear) / -11 (affine)
    gap_extend: int | None = None  # None -> -1; affine only
    prefilter: bool = False      # ungapped X-drop prefilter before full SW
    prefilter_min: int = 40      # skip full SW below this ungapped score
    xdrop: int | None = None     # X-drop termination margin; None is the
                                 # x->inf limit (plain best ungapped
                                 # segment): max recall AND fastest (the
                                 # run-best carry drops out of the scan)
    prefilter_batch: int = 256   # pairs per prefilter wave (the ungapped
                                 # scan is elementwise, so it batches wider)
    use_pallas: bool | None = None  # route waves (score-only and PID)
                                 # through the Pallas tile kernels; None =
                                 # auto (TPU only — interpret mode is
                                 # slower than the jnp wave off-TPU)
    pallas_interpret: bool | None = None  # kernel interpret override
                                 # (None = autodetect by backend)
    with_pid: bool = False       # also compute each pair's PID and
                                 # alignment length (linear gaps only)
    profile: bool = False        # block inside each ``wave`` span, so
                                 # the spans split gather/DP/drain time
                                 # (slower; no overlap)


@dataclass(frozen=True)
class PairScores:
    scores: np.ndarray           # (P,) int32 SW best score per input pair
                                 # (prefilter-rejected pairs: ungapped score,
                                 # a lower bound — see ``kept``)
    pid: np.ndarray | None       # (P,) float64 percent identity (with_pid)
    aln_len: np.ndarray | None   # (P,) int64 alignment length (with_pid)
    n_waves: int                 # jitted dispatches issued (incl. prefilter)
    n_shapes: int                # distinct wave shapes compiled
    ungapped: np.ndarray | None = None  # (P,) int32 prefilter scores
    kept: np.ndarray | None = None      # (P,) bool — pair ran full SW

    @property
    def n_prefiltered(self) -> int:
        return 0 if self.kept is None else int((~self.kept).sum())


def _quantize(lens: np.ndarray, quantum: int) -> np.ndarray:
    return np.maximum(quantum, -(-lens // quantum) * quantum)


def wave_plan(pairs: np.ndarray, lens: np.ndarray, cfg: WaveConfig):
    """Group pair indices into dispatch order by padded-length bucket.
    Yields (pair_idx (m,), Lq_pad, Lr_pad) with pair_idx referring to rows
    of ``pairs``, in input order within a bucket."""
    if len(pairs) == 0:
        return
    lq = _quantize(lens[pairs[:, 0]], cfg.len_quantum)
    lr = _quantize(lens[pairs[:, 1]], cfg.len_quantum)
    # dispatch key: the padded shape; lexsort is stable so pairs stay in
    # input order within a wave
    order = np.lexsort((lr, lq))
    keys = np.stack([lq[order], lr[order]], axis=1)
    starts = np.flatnonzero(
        np.concatenate([[True], (np.diff(keys, axis=0) != 0).any(axis=1)]))
    bounds = np.concatenate([starts, [len(order)]])
    for s, e in zip(bounds[:-1], bounds[1:]):
        yield order[s:e], int(keys[s, 0]), int(keys[s, 1])


# ---------------------------------------------------------------- device side
@functools.partial(jax.jit, static_argnames=("Lq", "Lr"))
@trace_sentinel("wave_gather")
@jax.named_scope("gather")
def _gather_wave(ids_dev, lens_dev, pi, pj, *, Lq: int, Lr: int):
    return (gather_rows(ids_dev, lens_dev, pi, Lq),
            gather_rows(ids_dev, lens_dev, pj, Lr))


@functools.partial(jax.jit, static_argnames=("x", "Lq", "Lr"))
@trace_sentinel("wave_ungapped")
def _wave_ungapped_device(ids_dev, lens_dev, pi, pj, *, x: int | None,
                          Lq: int, Lr: int):
    """Fused gather + ungapped X-drop prefilter scan."""
    qm, rm = _gather_wave(ids_dev, lens_dev, pi, pj, Lq=Lq, Lr=Lr)
    return ungapped_xdrop_scores(qm, rm, x=x)


@functools.lru_cache(maxsize=8)
def _sharded_wave_fns(devices: tuple):
    """SPMD wave programs over ``devices``: the (B,) pair index vectors
    split ``P("wave")`` (B a multiple of len(devices)), the corpus
    replicates, and every device gathers+scores its share of pairs inside
    ONE jitted program — the only dispatch form the CPU PJRT client
    actually runs concurrently. Per-pair results are independent, so the
    split is bit-exact with the single-device wave.

    Cached by the DEVICE TUPLE — the same keying discipline as the
    self-join emission and the serving ring (PR 5): device objects are
    per-process singletons, so every caller resolving the same devices —
    across fresh ``WaveConfig`` instances, fresh meshes, repeated
    ``score_pairs`` calls — shares one compiled program pair (cache
    stability pinned in tests/test_sharding.py). The previous key was the
    bare device *count*, which happened to coincide but broke the
    discipline (and would silently recompile nothing while masking a
    wrong-devices bug if callers ever passed a different prefix)."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(devices), ("wave",))
    ax = "wave"

    @functools.partial(jax.jit, static_argnames=(
        "Lq", "Lr", "dp_kernel", "gap_mode", "gap_open", "gap_extend"))
    @trace_sentinel("wave_sw_spmd", static_key=(devices,))
    def sw_fn(ids_dev, lens_dev, pi, pj, *, Lq: int, Lr: int,
              dp_kernel: str = "wavefront", gap_mode: str = "linear",
              gap_open: int | None = None, gap_extend: int | None = None):
        f = jax.shard_map(
            lambda i, l, a, b: sw_gather_scores(
                i, l, i, l, a, b, Lq=Lq, Lr=Lr, dp_kernel=dp_kernel,
                gap_mode=gap_mode, gap_open=gap_open,
                gap_extend=gap_extend),
            mesh=mesh, in_specs=(P(), P(), P(ax), P(ax)), out_specs=P(ax),
            check_vma=False)
        return f(ids_dev, lens_dev, pi, pj)

    @functools.partial(jax.jit, static_argnames=("x", "Lq", "Lr"))
    @trace_sentinel("wave_ungapped_spmd", static_key=(devices,))
    def ungapped_fn(ids_dev, lens_dev, pi, pj, *, x: int | None,
                    Lq: int, Lr: int):
        f = jax.shard_map(
            lambda i, l, a, b: ungapped_xdrop_scores(
                gather_rows(i, l, a, Lq), gather_rows(i, l, b, Lr), x=x),
            mesh=mesh, in_specs=(P(), P(), P(ax), P(ax)), out_specs=P(ax),
            check_vma=False)
        return f(ids_dev, lens_dev, pi, pj)

    return sw_fn, ungapped_fn


class _DrainRing:
    """FIFO of in-flight device results. JAX dispatch is async: pushing wave
    n+1 before fetching wave n overlaps its gather+DP with wave n's D2H
    transfer; only when the ring exceeds ``depth`` does the oldest result
    block on ``np.asarray`` (device_get), inside a ``drain`` span."""

    def __init__(self, depth: int, sink, kind: str):
        self.depth = max(0, depth)
        self.sink = sink                # sink(slots, host_values)
        self.kind = kind
        self._q: deque = deque()

    def push(self, slots, dev, B: int) -> None:
        self._q.append((slots, dev, B))
        while len(self._q) > self.depth:
            self._pop()

    def _pop(self) -> None:
        slots, dev, B = self._q.popleft()
        with span("drain", cat="allpairs", B=B, kind=self.kind):
            host = np.asarray(dev)
        self.sink(slots, host)

    def drain(self) -> None:
        while self._q:
            self._pop()


# ---------------------------------------------------------------- scheduler
class _WaveStats:
    def __init__(self):
        self.n_waves = 0
        self.shapes: set = set()


def _host_gather(ids, lens, pairs, chunk, B, Lq, Lr):
    """PR 2 path: assemble the wave with a per-pair host copy loop."""
    qm = np.full((B, Lq), PAD, np.int8)
    rm = np.full((B, Lr), PAD, np.int8)
    for n, p in enumerate(chunk):
        i, j = pairs[p]
        qm[n, :lens[i]] = ids[i, :lens[i]]
        rm[n, :lens[j]] = ids[j, :lens[j]]
    return qm, rm


def _pad_chunk(pairs, chunk, B):
    """Pair index vectors for one wave, -1-padded to the fixed batch B."""
    pi = np.full(B, -1, np.int32)
    pj = np.full(B, -1, np.int32)
    pi[:len(chunk)] = pairs[chunk, 0]
    pj[:len(chunk)] = pairs[chunk, 1]
    return pi, pj


def _score_block(qm, rm, kind: str, x: int | None, use_pallas: bool,
                 cfg: WaveConfig):
    """Score one assembled (B, Lq) x (B, Lr) block on device, routed by
    ``cfg.dp_kernel`` / ``cfg.gap_mode`` (see WaveConfig). ``kind="pid"``
    takes the Pallas PID kernel: (B, 3) score, identities, length."""
    if use_pallas:
        from ..kernels import ops
        if kind == "pid":
            return ops.wavefront_pid(qm, rm, interpret=cfg.pallas_interpret)
        if kind == "ungapped":
            return ops.ungapped_wave_scores(qm, rm, x=x,
                                            interpret=cfg.pallas_interpret)
        if cfg.dp_kernel == "wavefront":
            return ops.wavefront_scores(
                qm, rm, gap_mode=cfg.gap_mode, gap_open=cfg.gap_open,
                gap_extend=cfg.gap_extend, interpret=cfg.pallas_interpret)
        return ops.sw_wave_scores(qm, rm, interpret=cfg.pallas_interpret)
    if kind == "ungapped":
        return ungapped_xdrop_scores(qm, rm, x=x)
    if cfg.dp_kernel == "wavefront":
        from ..align.gotoh import sw_wave_affine, sw_wave_linear
        if cfg.gap_mode == "affine":
            kw = {} if cfg.gap_open is None else {"gap_open": cfg.gap_open}
            if cfg.gap_extend is not None:
                kw["gap_extend"] = cfg.gap_extend
            return sw_wave_affine(qm, rm, **kw)
        if cfg.gap_open is None:
            return sw_wave_linear(qm, rm)
        return sw_wave_linear(qm, rm, gap=cfg.gap_open)
    return sw_scores_device(jnp.asarray(qm), jnp.asarray(rm))


def _iter_wave_chunks(sub, lens, cfg: WaveConfig, wave_batch: int,
                      ndev: int = 1):
    """Shared wave-chunking skeleton: walk the dispatch plan, shrink the
    batch to the cell budget, and yield fixed-shape (chunk, B, Lq, Lr)
    work units (the last chunk of a bucket may be shorter than B — the
    dispatchers pad it). Single source of truth for the score and PID
    paths, so wave shapes can never diverge between them. ``wave_batch``
    and the cell budget are per-device: an SPMD wave (``ndev > 1``)
    carries ndev times the pairs per dispatch."""
    for idx, Lq, Lr in wave_plan(sub, lens, cfg):
        B = max(1, min(wave_batch, cfg.max_wave_cells // (Lq * Lr))) * ndev
        for s in range(0, len(idx), B):
            yield idx[s:s + B], B, Lq, Lr


def _store(out):
    """Drain sink writing a wave's results into ``out`` by pair slot."""
    def sink(slots, host):
        out[slots] = host[:len(slots)]
    return sink


def _run_score_waves(ids, lens, pairs, subset, cfg: WaveConfig, dev, sink,
                     stats: _WaveStats, *, kind: str, wave_batch: int,
                     use_pallas: bool, ndev: int = 1) -> None:
    """Dispatch waves (``kind``: "sw" | "ungapped", or "pid" through the
    Pallas PID kernel) over ``pairs[subset]``, handing each wave's results
    to ``sink(slots, host)`` through the async drain ring. With
    ``ndev > 1`` each wave is one SPMD program splitting its batch over
    the mesh (``_sharded_wave_fns``)."""
    sub = pairs[subset]
    sharded = (_sharded_wave_fns(tuple(jax.devices()[:ndev]))
               if ndev > 1 else None)
    ring = _DrainRing(0 if cfg.profile else cfg.inflight, sink, kind)
    for chunk, B, Lq, Lr in _iter_wave_chunks(sub, lens, cfg, wave_batch,
                                              ndev):
        if dev is None:                     # host-gather (PR 2) path
            with span("host_gather", cat="allpairs", B=B, n=len(chunk)):
                qm, rm = _host_gather(ids, lens, sub, chunk, B, Lq, Lr)
        # the span covers dispatch only: the device's work ends in the
        # ``drain`` span that fetches it, unless cfg.profile blocks here
        with span("wave", cat="allpairs", kind=kind, B=B, Lq=Lq, Lr=Lr,
                  n=len(chunk), spmd=ndev > 1):
            if dev is None:
                res = _score_block(qm, rm, kind, cfg.xdrop, use_pallas, cfg)
            elif use_pallas:                # device gather -> Pallas tile
                pi, pj = _pad_chunk(sub, chunk, B)
                qm, rm = _gather_wave(dev[0], dev[1], jnp.asarray(pi),
                                      jnp.asarray(pj), Lq=Lq, Lr=Lr)
                res = _score_block(qm, rm, kind, cfg.xdrop, True, cfg)
            elif sharded is not None:       # SPMD split over the mesh
                pi, pj = _pad_chunk(sub, chunk, B)
                sw_fn, ungapped_fn = sharded
                if kind == "ungapped":
                    res = ungapped_fn(dev[0], dev[1], pi, pj, x=cfg.xdrop,
                                      Lq=Lq, Lr=Lr)
                else:
                    res = sw_fn(dev[0], dev[1], pi, pj, Lq=Lq, Lr=Lr,
                                dp_kernel=cfg.dp_kernel,
                                gap_mode=cfg.gap_mode,
                                gap_open=cfg.gap_open,
                                gap_extend=cfg.gap_extend)
            elif kind == "ungapped":        # fused gather + scan
                pi, pj = _pad_chunk(sub, chunk, B)
                res = _wave_ungapped_device(dev[0], dev[1], pi, pj,
                                            x=cfg.xdrop, Lq=Lq, Lr=Lr)
            else:
                pi, pj = _pad_chunk(sub, chunk, B)
                res = sw_gather_scores(dev[0], dev[1], dev[0], dev[1],
                                       pi, pj, Lq=Lq, Lr=Lr,
                                       dp_kernel=cfg.dp_kernel,
                                       gap_mode=cfg.gap_mode,
                                       gap_open=cfg.gap_open,
                                       gap_extend=cfg.gap_extend)
            if cfg.profile:
                jax.block_until_ready(res)
        ring.push(subset[chunk], res, B)
        stats.n_waves += 1
        stats.shapes.add((kind, B, Lq, Lr))
    ring.drain()


def _run_pid_waves(ids, lens, pairs, subset, cfg: WaveConfig, dev,
                   scores, pid, aln, stats: _WaveStats) -> None:
    """Host PID route (off-TPU, the definition the kernel is tested
    against): the row wave's DP matrices, then the host traceback. The
    traceback is host-bound, so this route drains synchronously; the
    device gather still removes the per-pair copy loop."""
    sub = pairs[subset]
    for chunk, B, Lq, Lr in _iter_wave_chunks(sub, lens, cfg,
                                              cfg.wave_batch):
        if dev is None:
            with span("host_gather", cat="allpairs", B=B, n=len(chunk)):
                qm, rm = _host_gather(ids, lens, sub, chunk, B, Lq, Lr)
        else:
            pi, pj = _pad_chunk(sub, chunk, B)
            qm, rm = _gather_wave(dev[0], dev[1], jnp.asarray(pi),
                                  jnp.asarray(pj), Lq=Lq, Lr=Lr)
        # the whole PID wave: device DP + H-matrix D2H + host traceback
        # (sw_wave_pid interleaves them internally)
        with span("wave", cat="allpairs", kind="pid", B=B, Lq=Lq, Lr=Lr,
                  n=len(chunk)):
            pw, lw, sw = sw_wave_pid(qm, rm, chunk=B)
        slots = subset[chunk]
        pid[slots] = pw[:len(chunk)]
        aln[slots] = lw[:len(chunk)]
        scores[slots] = sw[:len(chunk)]
        stats.n_waves += 1
        stats.shapes.add(("pid", B, Lq, Lr))


def score_pairs(ids: np.ndarray, lens: np.ndarray, pairs: np.ndarray,
                cfg: WaveConfig | None = None) -> PairScores:
    """Score every (i, j) candidate pair with batched Smith-Waterman waves.

    ids (N, L) int8 PAD-padded corpus, lens (N,), pairs (P, 2) int32.
    Returns scores (and PID when ``cfg.with_pid``) aligned with ``pairs``.
    With ``cfg.prefilter`` the ungapped X-drop scan runs first and only
    survivors (``result.kept``) pay the full DP — their scores are bit-exact
    with the unfiltered path; rejected pairs report the ungapped lower
    bound (and PID 0).
    """
    cfg = cfg or WaveConfig()
    if cfg.dp_kernel not in ("wavefront", "rowwave"):
        raise ValueError(f"unknown dp_kernel {cfg.dp_kernel!r}")
    if cfg.gap_mode not in ("linear", "affine"):
        raise ValueError(f"unknown gap_mode {cfg.gap_mode!r}")
    if cfg.gap_mode == "affine":
        if cfg.dp_kernel == "rowwave":
            raise ValueError("affine gaps need dp_kernel='wavefront'")
        if cfg.with_pid:
            raise ValueError("with_pid needs gap_mode='linear' (the PID "
                             "traceback reads the linear-gap DP matrix)")
    pairs = np.asarray(pairs, np.int32)
    lens = np.asarray(lens, np.int32)
    P = len(pairs)
    with span("score_pairs", cat="allpairs", pairs=P) as sp:
        res = _score_pairs(ids, lens, pairs, cfg)
        sp.set(waves=res.n_waves, shapes=res.n_shapes,
               prefiltered=res.n_prefiltered)
    return res


def _score_pairs(ids, lens, pairs, cfg: WaveConfig) -> PairScores:
    P = len(pairs)
    scores = np.zeros(P, np.int32)
    pid = np.zeros(P) if cfg.with_pid else None
    aln = np.zeros(P, np.int64) if cfg.with_pid else None
    stats = _WaveStats()
    use_pallas = (cfg.use_pallas if cfg.use_pallas is not None
                  else on_tpu())
    dev = ((jnp.asarray(ids), jnp.asarray(lens))
           if cfg.device_gather and P else None)
    # SPMD wave split: only the jnp score/prefilter waves shard (the Pallas
    # kernels and the host PID route stay single-device)
    ndev = 1
    if dev is not None and not use_pallas:
        ndev = max(1, min(cfg.n_devices, jax.device_count()))

    everything = np.arange(P)
    ungapped = None
    kept = None
    subset = everything
    if cfg.prefilter and P:
        ungapped = np.zeros(P, np.int32)
        _run_score_waves(ids, lens, pairs, everything, cfg, dev,
                         _store(ungapped), stats, kind="ungapped",
                         wave_batch=cfg.prefilter_batch,
                         use_pallas=use_pallas, ndev=ndev)
        kept = ungapped >= cfg.prefilter_min
        scores[:] = ungapped        # lower bound for the rejected pairs
        subset = np.flatnonzero(kept)
    if len(subset):
        if cfg.with_pid and use_pallas:
            def sink(slots, host):
                n = len(slots)
                scores[slots] = host[:n, 0]
                aln[slots] = host[:n, 2]
                pid[slots] = 100.0 * host[:n, 1] / np.maximum(host[:n, 2], 1)

            _run_score_waves(ids, lens, pairs, subset, cfg, dev, sink,
                             stats, kind="pid", wave_batch=cfg.wave_batch,
                             use_pallas=True)
        elif cfg.with_pid:
            _run_pid_waves(ids, lens, pairs, subset, cfg, dev,
                           scores, pid, aln, stats)
        else:
            _run_score_waves(ids, lens, pairs, subset, cfg, dev,
                             _store(scores), stats, kind="sw",
                             wave_batch=cfg.wave_batch,
                             use_pallas=use_pallas, ndev=ndev)
    return PairScores(scores=scores, pid=pid, aln_len=aln,
                      n_waves=stats.n_waves, n_shapes=len(stats.shapes),
                      ungapped=ungapped, kept=kept)
