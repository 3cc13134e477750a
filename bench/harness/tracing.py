"""From a profiler trace and the program's spans to per-layer numbers.

``DeviceTrace`` runs ``jax.profiler`` over the measured window (no Python
tracer) with one ``TraceAnnotation`` marking the window, which puts the
program's ``perf_counter`` spans on the trace's clock. ``reduce`` is the
arithmetic the per-layer readers share: the union of device op intervals
(busy), kernel time by stable module name, and a ``breakdown`` of the
top device ops and the longest idle gaps, each gap named by the innermost
host span open at its midpoint.
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict

WINDOW_MARK = "bench_window"


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [a, b) intervals clipped to [lo, hi)."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def idle_gaps(intervals, lo: float, hi: float):
    """[(start, end)] of [lo, hi) not covered by any interval."""
    gaps, end = [], lo
    for a, b in sorted(intervals):
        if a > end and end < hi:
            gaps.append((end, min(a, hi)))
        end = max(end, b)
    if end < hi:
        gaps.append((end, hi))
    return gaps


def innermost(spans, t: float) -> str:
    """Name of the shortest span containing ``t`` ("no host span" if
    none: the host was between the program's spans, or waiting)."""
    best = None
    for s in spans:
        if s["dur"] is not None and s["ts"] <= t < s["ts"] + s["dur"]:
            if best is None or s["dur"] < best["dur"]:
                best = s
    return best["name"] if best is not None else "no host span"


def reduce(ops, modules, lo: float, hi: float, spans) -> dict:
    """``ops`` and ``modules``: [(name, start_s, end_s)] device events on
    the window's clock (seconds from the window start); ``spans``: host
    spans with ``ts``/``dur`` in the same seconds. Returns busy_s,
    window_s, op_s {op name: s}, module_s {module base name: s} and the
    breakdown lists."""
    iv = [(a, b) for _, a, b in ops]
    busy = union_seconds(iv, lo, hi)
    op_s, mod_s = defaultdict(float), defaultdict(float)
    for name, a, b in ops:
        op_s[name] += max(0.0, min(b, hi) - max(a, lo))
    for name, a, b in modules:
        mod_s[module_base(name)] += max(0.0, min(b, hi) - max(a, lo))
    gaps = sorted(idle_gaps(iv, lo, hi), key=lambda g: g[0] - g[1])[:10]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        busy_s=busy, window_s=hi - lo, op_s=dict(op_s), module_s=dict(mod_s),
        breakdown=dict(
            device_ops=[[n, s] for n, s in top],
            idle_gaps=[[innermost(spans, (a + b) / 2), b - a]
                       for a, b in gaps]))


def op_base(name: str) -> str:
    """``%fusion.5 = s32[...] fusion(...)`` -> ``fusion.5``: the HLO
    instruction's name, without its text."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_base(name: str) -> str:
    """``jit_wave_scores_kernel(1234)`` -> ``jit_wave_scores_kernel``."""
    return name.split("(", 1)[0]


def load_xplane(path: str):
    """Device op events, module events and the window mark from one
    ``.xplane.pb``: (ops, modules, mark_start_ns, mark_end_ns), events as
    (name, start_ns, end_ns) of the first device plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, mark = [], [], None
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None:
            device = plane
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        mark = (ev.start_ns, ev.end_ns)
    if device is not None:
        for line in device.lines:
            if line.name == "XLA Ops":
                ops = [(op_base(e.name), e.start_ns, e.end_ns)
                       for e in line.events]
            elif line.name == "XLA Modules":
                modules = [(e.name, e.start_ns, e.end_ns)
                           for e in line.events]
    return ops, modules, mark


class DeviceTrace:
    """``with DeviceTrace(dir) as dt: ...`` traces the enclosed window;
    ``dt.result(spans)`` reduces it (spans in seconds from
    ``dt.t_start``, the window's perf_counter start)."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def __enter__(self):
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self.t_start = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._ann.__exit__(None, None, None)
        self.t_end = time.perf_counter()
        jax.profiler.stop_trace()
        return False

    def result(self, spans) -> dict:
        paths = glob.glob(os.path.join(self.logdir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"no profiler trace under {self.logdir}")
        ops, modules, mark = load_xplane(max(paths, key=os.path.getmtime))
        if mark is None:
            raise RuntimeError("the trace has no window mark")
        m0 = mark[0]

        def rel(evs):
            return [(n, (a - m0) * 1e-9, (b - m0) * 1e-9) for n, a, b in evs]

        hi = (mark[1] - m0) * 1e-9
        ops, modules = rel(ops), rel(modules)
        out = reduce(ops, modules, 0.0, hi, spans)
        out["trace_bytes"] = sum(os.path.getsize(p) for p in paths)
        write_excerpt(os.path.join(self.logdir, "excerpt.json"), ops,
                      modules, spans, min(hi, 0.25))
        return out


def write_excerpt(path: str, ops, modules, spans, until: float) -> None:
    """The first ``until`` seconds of a reduced trace and what ``reduce``
    makes of them, as JSON: a small recorded trace for the tests."""
    import json

    def cut(evs):
        return [list(e) for e in evs if e[1] < until]

    ops_c, mod_c = cut(ops), cut(modules)
    sp = [dict(name=s["name"], ts=s["ts"], dur=s["dur"], args={
        k: v for k, v in s["args"].items() if k != "trace"})
        for s in spans if s["dur"] is not None and s["ts"] < until]
    r = reduce([tuple(e) for e in ops_c], [tuple(e) for e in mod_c], 0.0,
               until, sp)
    with open(path, "w") as fh:
        json.dump(dict(window_s=until, ops=ops_c, modules=mod_c, spans=sp,
                       busy_s=r["busy_s"], module_s=r["module_s"],
                       device_ops=r["breakdown"]["device_ops"]), fh)
