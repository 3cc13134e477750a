"""Percent-identity clustering: back-to-back whole-corpus
``all_pairs_search`` calls with the program's default scoring, every
candidate pair aligned and its percent identity (PID) computed, families
joined at PID >= ``min_pid``. Each clustering of the window is compared
with the plain reference (``reference_pid.py``) in pairs, DP scores, PID,
alignment lengths and families, all exactly."""
from __future__ import annotations

import time
import traceback
from types import SimpleNamespace

import numpy as np

from . import gen, reference, reference_pid
from .common import Cell, CompileCounter, ProgramSpans, memory_peak_bytes
from .tracing import DeviceTrace


def _program_config(cfg: dict):
    from repro.allpairs import AllPairsConfig, WaveConfig
    from repro.core import LSHConfig
    wave = WaveConfig(with_pid=cfg["with_pid"], prefilter=cfg["prefilter"],
                      gap_open=cfg["gap"])
    return AllPairsConfig(lsh=LSHConfig(**cfg["lsh"]), wave=wave,
                          min_pid=cfg["min_pid"])


def _answer(res) -> dict:
    """The program's clustering as host arrays, pairs in lexicographic
    order and families as a canonical partition."""
    pairs = np.asarray(res.pairs, np.int64)
    order = np.lexsort((pairs[:, 1], pairs[:, 0])) if len(pairs) else \
        np.zeros(0, np.int64)
    sc = res.scored
    return dict(pairs=pairs[order].astype(np.int32),
                scores=np.asarray(sc.scores)[order],
                pid=np.asarray(sc.pid)[order],
                aln_len=np.asarray(sc.aln_len)[order],
                labels=reference.canonical(res.labels))


def compare(got: dict, want: dict) -> dict:
    """How far one clustering lies from the reference: pairs in only one
    of the two sets, pairs whose DP score, PID or alignment length
    differs, and sequences whose family differs."""
    g = {tuple(p): i for i, p in enumerate(got["pairs"].tolist())}
    w = {tuple(p): i for i, p in enumerate(want["pairs"].tolist())}
    both = [(g[p], w[p]) for p in g.keys() & w.keys()]
    gi = np.array([a for a, _ in both], np.int64)
    wi = np.array([b for _, b in both], np.int64)

    def diff(key):
        return int(np.sum(got[key][gi] != want[key][wi]))

    return dict(pair_set_diff=len(g.keys() ^ w.keys()),
                dp_diff=diff("scores"), pid_diff=diff("pid"),
                aln_len_diff=diff("aln_len"),
                family_diff=int(np.sum(got["labels"] != want["labels"])))


def control(cfg: dict, seed: int) -> dict:
    """The reference in bfloat16 compared with the exact one, as a run
    compares each clustering of its window."""
    corpus = gen.family_corpus(seed, **cfg["corpus"])
    want = reference_pid.allpairs_pid(corpus["ids"], corpus["lens"], cfg)
    low = reference_pid.allpairs_pid(corpus["ids"], corpus["lens"], cfg,
                                     low=True)
    return compare(low, want)


def run(cell: Cell, counter: CompileCounter) -> dict:
    from repro.allpairs import all_pairs_search
    cfg = cell.config
    corpus = gen.family_corpus(cell.seed, **cfg["corpus"])
    ids, lens = corpus["ids"], corpus["lens"]
    apc = _program_config(cfg)
    c_setup = counter.snapshot()
    warm = all_pairs_search(ids, lens, apc)        # compiles every shape
    n_waves, n_shapes = warm.scored.n_waves, warm.scored.n_shapes
    del warm

    spans = ProgramSpans(cell.trace)
    dt = DeviceTrace(str(cell.scratch / "trace")) if cell.trace else None
    jobs, answers, errors = [], [], []
    c0 = counter.snapshot()
    if dt is not None:
        dt.__enter__()
    w0 = dt.t_start if dt is not None else time.perf_counter()
    while True:
        t0 = time.perf_counter()
        try:
            res = all_pairs_search(ids, lens, apc)
        except Exception:       # noqa: BLE001 — a failed job is reported
            errors.append(traceback.format_exc(limit=3))
            res = None
        t1 = time.perf_counter()
        jobs.append((t0 - w0, t1 - w0))
        if res is not None:
            answers.append(_answer(res))
        del res
        if t1 - w0 >= cell.seconds or len(errors) > 2:
            break
    if dt is not None:
        dt.__exit__(None, None, None)
    c1 = counter.snapshot()
    setup_s = w0 - cell.t_process
    window = CompileCounter.delta(c0, c1)
    host_spans = spans.collect(w0)
    if cell.trace:      # the benchmark's own span around each job
        host_spans += [dict(name="all_pairs_search", cat="bench", ts=a,
                            dur=b - a, args={}) for a, b in jobs]
    device = dt.result(host_spans) if dt is not None else None
    peak = memory_peak_bytes(cell.chips)

    t_ref = time.perf_counter()
    want = reference_pid.allpairs_pid(ids, lens, cfg)
    ref_s = time.perf_counter() - t_ref
    worst = dict(pair_set_diff=0, dp_diff=0, pid_diff=0, aln_len_diff=0,
                 family_diff=0)
    for got in answers:
        for k, v in compare(got, want).items():
            worst[k] = max(worst[k], v)
    checks = [(k, v, 0) for k, v in worst.items()]
    checks.append(("window_compiles", window["compiles"] + window["sentinel"],
                   0))
    checks.append(("failed_jobs", len(errors), 0))
    n = len(jobs)
    return dict(
        e2e=dict(cluster_s=(jobs[-1][1] - jobs[0][0]) / n, setup_s=setup_s),
        obs=SimpleNamespace(spans=host_spans, jobs=jobs, device=device),
        checks=checks, attempted=n, failed=len(errors),
        memory_peak_bytes=peak,
        notes=dict(
            clusterings=n, pairs=len(want["pairs"]),
            edges=int((want["pid"] >= cfg["min_pid"]).sum()),
            mean_aln_len=float(want["aln_len"].mean()) if len(want["pairs"])
            else 0.0,
            waves=n_waves, wave_shapes=n_shapes,
            reference_s=round(ref_s, 2),
            setup_compiles=CompileCounter.delta(c_setup, c0),
            window_lowerings=window["lowerings"],
            window_cache_hits=window["cache_hits"],
            errors=errors[:1]))
