"""Structured tracing: spans, per-query trace IDs, Chrome/Perfetto export.

The paper's evaluation instrument is per-phase time attribution (map /
shuffle / reduce wall-clock per node); the extreme-scale follow-up
(PAPERS.md) keeps the same discipline at thousands of nodes. This module
is that instrument for our stack: a zero-dependency span API whose
records land in a bounded, thread-safe ring buffer and export as Chrome
``trace_event`` JSON — one ``--trace-out`` file from an SLO sweep opens
directly in ``chrome://tracing`` / Perfetto with every serving thread,
lifecycle event, and compile on one timeline.

Design rules:

* **disabled tracing is one branch** — :func:`span` checks a module
  global and returns a shared no-op context manager; no allocation, no
  lock, no clock read. Tracing is off by default; the serving hot path
  pays ~a dict construction per call site (the ``**attrs``) and nothing
  else.
* **trace IDs are minted at the front door and ride a contextvar** —
  :meth:`repro.serve.engine.AsyncEngine.submit` mints one ID per query;
  the dispatch thread enters :func:`trace_context` with the IDs of the
  batch it assembled, so every span recorded beneath it (router pick,
  replica probe, ring sweep, re-rank) is automatically tagged with the
  queries it served. A batch span carries *all* its queries' IDs — that
  is the honest shape: micro-batched serving does work for many queries
  at once, and attribution must say so rather than pretend per-query
  isolation.
* **bounded buffer** — a ``deque(maxlen=capacity)``; a week of always-on
  serving cannot OOM the tier, the newest spans win.

Span taxonomy (see README "Observability" for the full glossary):

==========  ================================================================
category    spans
==========  ================================================================
serve       submit, dispatch, shed, query_batch, ladder, sig, probe, ring,
            rerank, route, resolve, warmup
lifecycle   seal, refresh, place, compact_serving, ingest, minor_compaction,
            major_compaction, compact_index, index_build
allpairs    emission, delta_emission, wave, drain, score_pairs, graph
jit         compile (instant; one per traced program body — see
            repro.obs.jit); lower (a JAX trace, lowering, backend compile
            or persistent-cache load, from ``jax.monitoring``)
runtime     gc (one Python garbage collection, from ``gc.callbacks``)
==========  ================================================================

The ``gc`` and ``lower`` spans are host stalls no call site can wrap:
:meth:`Tracer.enable` installs their hooks (importing JAX then, not when
this module is imported) and :meth:`Tracer.disable` takes the ``gc``
callback out again; the JAX listener stays registered and returns at once
while tracing is off. While a ``jax.profiler`` trace is running, every
:func:`span` also opens a ``jax.profiler.TraceAnnotation`` of its name, so
the profile shows the program's spans on the device trace's own clock.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import json
import os
import threading
import time
from collections import deque

__all__ = [
    "TRACER", "Tracer", "span", "instant", "record", "new_trace_id",
    "trace_context", "current_trace", "enable", "disable",
]

#: trace IDs of the queries the current thread is doing work for
#: (a tuple: a dispatch batch serves many queries at once).
_TRACE_CTX: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_trace", default=())

_ids = itertools.count(1)       # CPython next() is atomic

#: ``jax.profiler.TraceAnnotation`` once a tracer was enabled (None before:
#: importing this module must not import JAX)
_annotation = None

#: ``jax.monitoring`` duration events recorded as ``lower`` spans, by the
#: ``event`` arg they get: each is host time spent making a program
_LOWER_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def new_trace_id() -> int:
    """Mint a process-unique trace ID (one per submitted query)."""
    return next(_ids)


@contextlib.contextmanager
def trace_context(ids: tuple):
    """Tag every span recorded in this context with ``ids`` (the queries
    the enclosed work serves). Nesting replaces, not extends — the inner
    scope knows best which queries it serves."""
    tok = _TRACE_CTX.set(tuple(ids))
    try:
        yield
    finally:
        _TRACE_CTX.reset(tok)


def current_trace() -> tuple:
    return _TRACE_CTX.get()


class Tracer:
    """Bounded thread-safe span buffer + Chrome trace_event export."""

    def __init__(self, capacity: int = 65536):
        self.enabled = False
        self._buf: deque = deque(maxlen=int(capacity))
        # re-entrant: a garbage collection can start between any two
        # bytecodes, also inside a locked block, and its callback records
        self._lock = threading.RLock()
        self._t0 = time.perf_counter()      # trace epoch (ts are relative)
        self._dropped = 0
        self._gc_t0: float | None = None
        self._jax_hooked = False

    # -------------------------------------------------------------- control
    def enable(self, capacity: int | None = None) -> None:
        with self._lock:
            if capacity is not None:
                self._buf = deque(self._buf, maxlen=int(capacity))
            self.enabled = True
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        if not self._jax_hooked:
            self._jax_hooked = True
            global _annotation
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                self._on_jax_duration)
            _annotation = jax.profiler.TraceAnnotation

    def disable(self) -> None:
        self.enabled = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    # -------------------------------------------------------------- hooks
    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: one ``gc`` span per collection."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            t0, self._gc_t0 = self._gc_t0, None
            if self.enabled:
                self.record("gc", "runtime", t0, time.perf_counter(),
                            {"generation": info["generation"],
                             "collected": info["collected"]})

    def _on_jax_duration(self, event: str, secs: float, **kw) -> None:
        """``jax.monitoring`` listener: a ``lower`` span over the last
        ``secs`` seconds, recorded as the event ends."""
        if not self.enabled or event not in _LOWER_EVENTS:
            return
        t1 = time.perf_counter()
        self.record("lower", "jit", t1 - secs, t1,
                    {"event": _LOWER_EVENTS[event],
                     "fun": kw.get("fun_name")})

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._t0 = time.perf_counter()

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    # -------------------------------------------------------------- record
    def record(self, name: str, cat: str, t0: float, t1: float | None,
               attrs: dict | None = None) -> None:
        """Append one span (t0/t1 are ``perf_counter`` seconds; ``t1=None``
        records an instant event). Auto-tags the current trace context."""
        args = dict(attrs) if attrs else {}
        if "trace" not in args:
            trace = _TRACE_CTX.get()
            if trace:
                args["trace"] = list(trace)
        ev = (name, cat, t0 - self._t0, None if t1 is None else t1 - t0,
              threading.get_ident(), threading.current_thread().name, args)
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(ev)

    # -------------------------------------------------------------- read
    def spans(self) -> list[dict]:
        """Snapshot as dicts: {name, cat, ts (s), dur (s or None), tid,
        thread, args} — ``args["trace"]`` holds the query trace IDs."""
        with self._lock:
            evs = list(self._buf)
        return [dict(name=n, cat=c, ts=ts, dur=dur, tid=tid, thread=thr,
                     args=args) for n, c, ts, dur, tid, thr, args in evs]

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON object (open in ``chrome://tracing``
        or https://ui.perfetto.dev). Durations are complete ("X") events in
        microseconds; instants are "i" events; thread names ride metadata
        ("M") events so Perfetto labels the serving threads."""
        pid = os.getpid()
        events = []
        threads = {}
        with self._lock:
            evs = list(self._buf)
            dropped = self._dropped
        for name, cat, ts, dur, tid, thread, args in evs:
            threads.setdefault(tid, thread)
            ev = {"name": name, "cat": cat, "pid": pid, "tid": tid,
                  "ts": ts * 1e6, "args": args}
            if dur is None:
                ev.update(ph="i", s="t")
            else:
                ev.update(ph="X", dur=dur * 1e6)
            events.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                 "args": {"name": thread}} for tid, thread in threads.items()]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": dropped}}

    def export(self, path) -> int:
        """Write the Chrome trace JSON; returns the number of span events."""
        obj = self.chrome_trace()
        with open(path, "w") as fh:
            json.dump(obj, fh)
        return len(obj["traceEvents"])


TRACER = Tracer()


def enable(capacity: int | None = None) -> None:
    TRACER.enable(capacity)


def disable() -> None:
    TRACER.disable()


class _NopSpan:
    """Shared do-nothing context manager: the disabled-tracing fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOP = _NopSpan()


class _Span:
    __slots__ = ("name", "cat", "attrs", "t0", "ann")

    def __init__(self, name: str, cat: str, attrs: dict):
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def __enter__(self):
        self.ann = None
        if _annotation is not None and _annotation.is_enabled():
            self.ann = _annotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        TRACER.record(self.name, self.cat, self.t0, t1, self.attrs)
        return False

    def set(self, **attrs) -> None:
        """Add args known only at the end of the span's work."""
        self.attrs.update(attrs)


def span(name: str, cat: str = "serve", **attrs):
    """``with span("probe", shard=s): ...`` — records a complete event when
    tracing is enabled; a shared no-op otherwise (one branch). ``with
    span(...) as sp: ...; sp.set(n=...)`` adds args at the end."""
    if not TRACER.enabled:
        return _NOP
    return _Span(name, cat, attrs)


def instant(name: str, cat: str = "serve", **attrs) -> None:
    """Record a zero-duration marker (submit/resolve/shed/compile)."""
    if TRACER.enabled:
        TRACER.record(name, cat, time.perf_counter(), None, attrs)


def record(name: str, t0: float, t1: float, cat: str = "serve",
           **attrs) -> None:
    """Record a span from timestamps already measured (for call sites that
    keep their own ``perf_counter`` bookkeeping, e.g. the engine's stage
    timers — no double clock reads on the hot path)."""
    if TRACER.enabled:
        TRACER.record(name, cat, t0, t1, attrs)
