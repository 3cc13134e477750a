"""Open-loop traffic: a seeded arrival schedule from a traffic file, and a
client that submits on it and times every request from when it was due.

A schedule is a Poisson process whose rate is piecewise constant over
``period_s``: the traffic file's ``phases`` (each a ``rate_qps`` and a
``share`` of the period) repeat in order. A steady mix has one phase.
Arrivals are drawn by time-rescaling: a unit-rate process is mapped
through the inverse of the cumulative rate. Its gaps are the stratified
quantiles of the exponential law in a seeded order, so every seed offers
the same number of arrivals and the same set of gaps, in another order.

The client is ``benchmarks/serve_slo.py::_open_loop_point`` with two
faults fixed: latency runs from the due time, not from the actual submit,
so a stalled client cannot hide a stall; and the schedule is the Poisson
one above, not evenly spaced.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .gen import rng_for


def _phases(traffic: dict, seconds: float):
    """[(t0, t1, rate)] segments covering [0, seconds)."""
    phases = traffic["phases"]
    period = float(traffic.get("period_s", seconds))
    out, t = [], 0.0
    while t < seconds:
        for ph in phases:
            t1 = min(t + float(ph["share"]) * period, seconds)
            if t1 > t:
                out.append((t, t1, float(ph["rate_qps"])))
            t = t1
            if t >= seconds:
                break
    return out


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the window start, ascending) of every
    request the mix offers in ``seconds``."""
    segs = _phases(traffic, seconds)
    cum = np.cumsum([0.0] + [(b - a) * r for a, b, r in segs])
    m = int(round(cum[-1]))
    if m == 0:
        return np.zeros(0)
    q = (np.arange(m) + 0.5) / m
    gaps = -np.log1p(-q)[rng_for(seed, 3).permutation(m)]
    u = np.cumsum(gaps)
    u *= cum[-1] * (1.0 - 0.5 / m) / u[-1]
    k = np.clip(np.searchsorted(cum, u, side="right") - 1, 0, len(segs) - 1)
    t0 = np.array([s[0] for s in segs])[k]
    rate = np.array([s[2] for s in segs])[k]
    return t0 + (u - cum[k]) / rate


class OpenLoop:
    """Submit ``payload(i)`` at ``t0 + due[i]`` (``time.perf_counter``
    clock) from the calling thread; completion times come from the
    futures' callbacks. ``latency_s`` is completion minus due time;
    ``late_s`` is how late each submit left against its due time."""

    def __init__(self, submit, payload, due: np.ndarray):
        self.submit = submit
        self.payload = payload
        self.due = np.asarray(due, np.float64)
        n = len(self.due)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.futures: list = [None] * n
        self._left = n
        self._cv = threading.Condition()

    def _on_done(self, i: int):
        def cb(_fut):
            t = time.perf_counter()
            with self._cv:
                self.done[i] = t
                self._left -= 1
                self._cv.notify_all()
        return cb

    def run(self, t0: float) -> None:
        self.t0 = t0
        for i, d in enumerate(self.due):
            target = t0 + d
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            self.sent[i] = time.perf_counter()
            fut = self.submit(self.payload(i))
            self.futures[i] = fut
            fut.add_done_callback(self._on_done(i))

    def wait(self, timeout: float) -> bool:
        """Wait until every future has resolved or ``timeout`` passed."""
        end = time.perf_counter() + timeout
        with self._cv:
            while self._left > 0:
                left = end - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True

    @property
    def latency_s(self) -> np.ndarray:
        return self.done - (self.t0 + self.due)

    @property
    def late_s(self) -> np.ndarray:
        return self.sent - (self.t0 + self.due)
