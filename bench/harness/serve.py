"""Search serving: open-loop query traffic through ``AsyncEngine.submit``
into a ``ReplicaFleet`` over one reference index, every answer checked."""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from . import gen, reference, traffic
from .common import Cell, CompileCounter, ProgramSpans, memory_peak_bytes
from .tracing import DeviceTrace

WAIT_AFTER_S = 60.0     # how long past the window's close answers may come


class Service:
    """The system under test, built and warmed from a configuration."""

    def __init__(self, cfg: dict, seed: int, pool: int):
        from repro.core import LSHConfig
        from repro.index import ServingConfig, SignatureIndex
        from repro.serve import AsyncEngine, ReplicaFleet
        self.cfg = cfg
        self.data = gen.protein_sets(seed, n_queries=pool, **cfg["refs"],
                                     **cfg["queries"])
        d = self.data
        self.rows = [d["query_ids"][j, :d["query_lens"][j]]
                     for j in range(pool)]
        self.index = SignatureIndex.build(LSHConfig(**cfg["lsh"]),
                                          d["ref_ids"], d["ref_lens"])
        scfg = ServingConfig(k=cfg["k"], max_batch=cfg["max_batch"],
                             mode="probe")
        self.fleet = ReplicaFleet(self.index, scfg,
                                  n_replicas=cfg["replicas"])
        # every batch rung x length quantum the pool can reach, and the
        # probe cap settled over the whole pool
        self.fleet.warmup(d["query_ids"], d["query_lens"])
        self.engine = AsyncEngine(self.fleet,
                                  max_wait_ms=cfg["max_wait_ms"],
                                  queue_depth=cfg["queue_depth"])
        # one pass of the pool through the async path
        futs = [self.engine.submit(r) for r in self.rows]
        for f in futs:
            f.result(timeout=600)

    def offer(self, due: np.ndarray, pick: np.ndarray, t0: float):
        """Run one open-loop window from ``t0``; returns the client."""
        client = traffic.OpenLoop(self.engine.submit,
                                  lambda i: self.rows[pick[i]], due)
        client.run(t0)
        client.wait(max(0.0, t0 + (due[-1] if len(due) else 0.0)
                        + WAIT_AFTER_S - time.perf_counter()))
        return client

    def close(self) -> None:
        self.engine.close()
        self.fleet.close()
        del self.engine, self.fleet, self.index
        gc.collect()


def outcomes(client, limit_ms: float, end: float):
    """Per request: latency in ms (unanswered, refused or degraded
    requests count as the time until ``end``), and its outcome."""
    from repro.serve import Completed, Degraded, Rejected
    lat = client.latency_s * 1e3
    kind = []
    for i, f in enumerate(client.futures):
        if f is None or not f.done():
            kind.append("unanswered")
            continue
        r = f.result()
        kind.append("completed" if isinstance(r, Completed) else
                    "degraded" if isinstance(r, Degraded) else
                    f"rejected_{r.reason}" if isinstance(r, Rejected)
                    else "unknown")
    kind = np.array(kind)
    miss = kind != "completed"
    lat = np.where(miss, (end - (client.t0 + client.due)) * 1e3, lat)
    return lat, kind


def run(cell: Cell, counter: CompileCounter) -> dict:
    cfg, tr = cell.config, cell.traffic
    svc = Service(cfg, cell.seed, tr["pool"])
    due = traffic.arrivals(tr, cell.seed, cell.seconds)
    pick = gen.rng_for(cell.seed, 4).permutation(len(due)) % tr["pool"]

    spans = ProgramSpans(cell.trace)
    dt = DeviceTrace(str(cell.scratch / "trace")) if cell.trace else None
    c0 = counter.snapshot()
    if dt is not None:
        dt.__enter__()
    w0 = dt.t_start if dt is not None else time.perf_counter()
    client = svc.offer(due, pick, w0 + 0.001)
    if dt is not None:
        dt.__exit__(None, None, None)
    c1 = counter.snapshot()
    end = time.perf_counter()
    setup_s = w0 - cell.t_process
    window = CompileCounter.delta(c0, c1)
    host_spans = spans.collect(w0)
    device = dt.result(host_spans) if dt is not None else None
    peak = memory_peak_bytes(cell.chips)

    limit = float(tr["latency_limit_ms"])
    lat, kind = outcomes(client, limit, end)
    done = kind == "completed"
    results = [f.result() if d else None
               for f, d in zip(client.futures, done)]
    got_ids = np.stack([r.ids for r, d in zip(results, done) if d]) \
        if done.any() else np.zeros((0, cfg["k"]), np.int32)
    got_d = np.stack([r.dists for r, d in zip(results, done) if d]) \
        if done.any() else np.zeros((0, cfg["k"]), np.int32)
    queued = [r.queued_ms for r, d in zip(results, done) if d]
    late = client.late_s
    svc.close()

    # every answer of the window against the reference's answer for its
    # query
    d, lsh = svc.data, cfg["lsh"]
    kw = dict(k=lsh["k"], T=lsh["T"], f=lsh["f"], scheme=lsh["scheme"])
    r_sig, r_ok = reference.signatures(d["ref_ids"], d["ref_lens"], **kw)
    q_sig, q_ok = reference.signatures(d["query_ids"], d["query_lens"], **kw)
    want_ids, want_d = reference.answers(r_sig, r_ok, q_sig, q_ok, f=lsh["f"],
                                         bands=lsh["d"] + 1, k=cfg["k"])
    p = pick[done]
    wrong = int(np.sum(np.any((got_ids != want_ids[p])
                              | (got_d != want_d[p]), axis=1)))
    failed = int(np.sum((kind == "degraded") | (kind == "rejected_internal")
                        | (kind == "unknown")))
    checks = [("wrong_answers", wrong, 0),
              ("unanswered", int(np.sum(kind == "unanswered")), 0),
              ("failed_requests", failed, 0),
              ("window_compiles", window["compiles"] + window["sentinel"], 0),
              ("no_answer_compared", int(not done.any()), 0)]
    n = len(due)
    kinds, counts = np.unique(kind, return_counts=True)
    return dict(
        e2e=dict(search_p50_ms=float(np.quantile(lat, 0.5)),
                 search_p95_ms=float(np.quantile(lat, 0.95)),
                 search_goodput_qps=float(np.sum(done & (lat <= limit))
                                          / cell.seconds),
                 setup_s=setup_s),
        obs=SimpleNamespace(spans=host_spans, device=device,
                            queued_ms=queued),
        checks=checks, attempted=n, failed=int(n - done.sum()),
        memory_peak_bytes=peak,
        notes=dict(
            offered=n, outcomes={str(a): int(b) for a, b in
                                 zip(kinds, counts)},
            answers_compared=int(done.sum()),
            client_late_ms=dict(p50=float(np.nanquantile(late, 0.5) * 1e3),
                                p99=float(np.nanquantile(late, 0.99) * 1e3),
                                max=float(np.nanmax(late) * 1e3))
            if n else {},
            window_lowerings=window["lowerings"],
            window_cache_hits=window["cache_hits"]))
