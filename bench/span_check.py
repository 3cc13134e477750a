#!/usr/bin/env python3
"""What the program's spans cost, and how well they sit on the profiler's
clock, in one all-pairs cell on the chip.

    python3 bench/span_check.py --workload ecoli_cluster --seed <n> \\
        --seconds <s> [--pairs 3]

One process, two measurements:

1. Clock drift. One traced window, made as ``bench/run.py --trace 1``
   makes it (its result line is printed to stderr). Each program span that
   also reached the profiler as a ``TraceAnnotation`` is then paired, by
   name and order, with its TraceMe event on the host plane, and the
   harness's ``perf_counter`` placement of the span is compared with the
   event, both from the window's start: at the window's first spans and
   at its last.
2. Cost. Windows of back-to-back clusterings of the cell's corpus,
   alternately with the program's spans off and on (the ``repro.obs``
   tracer with its gc and JAX hooks, no profiler), ``--pairs`` of each.
   ``cluster_s`` of every window and the difference of the medians.

Besides, from the traced window: time per clustering in each span
name; device time by program and the 40 longest device ops by name
(where the kernels' op names can be read); the ``gc`` and ``lower``
spans by generation and event; and each ``wave`` or ``drain`` span of
half a second or more, with the long host events (any thread, the
runtime's own TraceMe events too) that overlap it.

The last stdout line is one JSON object. Without a TPU it exits 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
EDGE = 100          # spans averaged at each end of the window
LONG_S = 0.05       # host events at least this long are kept whole
STALL_S = 0.5       # wave and drain spans at least this long are explained


def host_events(path: str, names: set):
    """The window mark's start (ns), {name: [(start_ns, end_ns)]} of the
    host plane's events named in ``names``, and every host event of at
    least ``LONG_S`` as (thread line, name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    from harness.tracing import WINDOW_MARK
    mark, evs, long = None, defaultdict(list), []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_MARK:
                    mark = ev.start_ns
                    continue
                if ev.name in names:
                    evs[ev.name].append((ev.start_ns, ev.end_ns))
                if ev.end_ns - ev.start_ns >= LONG_S * 1e9:
                    long.append((line.name, ev.name, ev.start_ns, ev.end_ns))
    return mark, evs, long


def stalls(spans) -> dict:
    """Count, total and longest seconds of the ``gc`` spans by generation
    and the ``lower`` spans by event, over the window."""
    out = {}
    for s in spans:
        if s["name"] in ("gc", "lower") and s["dur"] is not None:
            a = s["args"]
            key = f"{s['name']}:{a.get('generation', a.get('event'))}"
            n, t, mx = out.get(key, (0, 0.0, 0.0))
            out[key] = (n + 1, t + s["dur"], max(mx, s["dur"]))
    return {k: dict(n=n, total_s=t, max_s=mx) for k, (n, t, mx) in out.items()}


def totals(spans, jobs: int) -> dict:
    """Milliseconds per clustering in each span name, and the count."""
    out = defaultdict(lambda: [0, 0.0])
    for s in spans:
        if s["dur"] is not None:
            out[s["name"]][0] += 1
            out[s["name"]][1] += s["dur"]
    return {k: dict(n=n, ms_per_job=1e3 * t / jobs)
            for k, (n, t) in sorted(out.items())}


def long_spans(spans, long, mark_ns: int) -> list:
    """Each ``wave`` or ``drain`` span of at least ``STALL_S``, with the
    host events of at least ``LONG_S`` (any thread) that overlap it."""
    rows = []
    for s in spans:
        if s["name"] not in ("wave", "drain") or s["dur"] is None \
                or s["dur"] < STALL_S:
            continue
        a, b = s["ts"], s["ts"] + s["dur"]
        near = [dict(thread=t, name=n[:120], ts=(x - mark_ns) * 1e-9,
                     dur=(y - x) * 1e-9)
                for t, n, x, y in long
                if (x - mark_ns) * 1e-9 < b and (y - mark_ns) * 1e-9 > a]
        near.sort(key=lambda r: r["dur"])
        rows.append(dict(name=s["name"], ts=a, dur=s["dur"],
                         args={k: v for k, v in s["args"].items()
                               if k != "trace"}, host_events=near[:12]))
    return rows


def drift(spans, mark_ns: int, evs: dict, window_s: float) -> dict:
    """Placement error, in ms, of the spans (``ts``/``dur`` in seconds from
    the window's start, as the harness places them) against their TraceMe
    events: event start less span start, and the same for the ends, as
    the median over the first and over the last ``EDGE`` pairs."""
    pairs, skipped = [], {}
    for name, got in evs.items():
        mine = sorted((s for s in spans if s["name"] == name
                       and s["dur"] is not None and s["ts"] >= 0
                       and s["ts"] + s["dur"] <= window_s),
                      key=lambda s: s["ts"])
        if len(mine) != len(got):
            skipped[name] = [len(mine), len(got)]
            continue
        for s, (a, b) in zip(mine, sorted(got)):
            pairs.append((s["ts"], (a - mark_ns) * 1e-9 - s["ts"],
                          (b - mark_ns) * 1e-9 - s["ts"] - s["dur"]))
    if not pairs:
        return dict(matched=0, skipped=skipped)
    pairs.sort()

    def med(rows, i):
        return 1e3 * statistics.median(r[i] for r in rows)

    head, tail = pairs[:EDGE], pairs[-EDGE:]
    return dict(matched=len(pairs), skipped=skipped,
                first_ts_s=pairs[0][0], last_ts_s=pairs[-1][0],
                start_ms_at_first=med(head, 1), end_ms_at_first=med(head, 2),
                start_ms_at_last=med(tail, 1), end_ms_at_last=med(tail, 2),
                max_abs_ms=1e3 * max(max(abs(r[1]), abs(r[2]))
                                     for r in pairs))


def window(ids, lens, apc, seconds: float) -> tuple[float, int]:
    """``cluster_s`` and the clusterings of one window."""
    from repro.allpairs import all_pairs_search
    jobs = []
    w0 = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        all_pairs_search(ids, lens, apc)
        t1 = time.perf_counter()
        jobs.append((t0, t1))
        if t1 - w0 >= seconds:
            break
    return (jobs[-1][1] - jobs[0][0]) / len(jobs), len(jobs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=3,
                    help="off/on window pairs of the cost measurement "
                         "(0: the traced window alone)")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    if config["operation"] != "allpairs":
        print("span_check measures all-pairs cells only", file=sys.stderr)
        return 2
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{wl['traffic']}.json").read_text())

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import allpairs, gen
    from harness.common import (Cell, CompileCounter, device_info,
                                use_compile_cache)
    use_compile_cache()
    counter = CompileCounter()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"needs a TPU; JAX found {dev['platform']}", file=sys.stderr)
        return 3
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH_DIR / "run.py")
    run_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_mod)

    # 1. one traced window, as bench/run.py --trace 1 makes it
    cell = Cell(name=wl["name"], config=config, traffic=traffic,
                seed=args.seed, seconds=args.seconds, trace=True,
                chips=wl["chips"],
                scratch=ROOT / ".bench_out" / f"{wl['name']}-span-check",
                t_process=T_PROCESS)
    cell.scratch.mkdir(parents=True, exist_ok=True)
    out = allpairs.run(cell, counter)
    result = run_mod.result_line(bench, wl["name"], out, dev, True)
    traced_s = time.perf_counter() - T_PROCESS
    print("[traced] " + json.dumps(result), file=sys.stderr, flush=True)
    spans = out["obs"].spans
    paths = glob.glob(os.path.join(cell.scratch, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    names = {s["name"] for s in spans if s["cat"] not in ("jit", "runtime",
                                                          "bench")}
    mark, evs, long = host_events(max(paths, key=os.path.getmtime), names)
    clock = drift(spans, mark, evs, out["obs"].device["window_s"])
    print("[drift] " + json.dumps(clock), file=sys.stderr, flush=True)
    explained = long_spans(spans, long, mark)

    # 2. spans off / on, profiler off, same corpus
    from repro.obs import TRACER
    corpus = gen.family_corpus(args.seed, **config["corpus"])
    apc = allpairs._program_config(config)
    cost = {"off": [], "on": []}
    for _ in range(args.pairs):
        for mode in ("off", "on"):
            if mode == "on":
                TRACER.enable(1 << 21)
                TRACER.clear()
            c, n = window(corpus["ids"], corpus["lens"], apc, args.seconds)
            n_spans = len(TRACER) if mode == "on" else 0
            TRACER.disable()
            TRACER.clear()
            cost[mode].append(c)
            print(f"[cost] spans {mode}: cluster_s {c} over {n} "
                  f"clusterings, {n_spans} spans", file=sys.stderr,
                  flush=True)
    if args.pairs:
        off = statistics.median(cost["off"])
        on = statistics.median(cost["on"])
        cost.update(off_median=off, on_median=on,
                    cost_pct=100.0 * (on - off) / off)
    d = out["obs"].device
    print(json.dumps(dict(
        device=dev, seed=args.seed, seconds=args.seconds,
        traced_run_s=traced_s, checks=result["checks"],
        correct=result["correct"], metrics=result["metrics"],
        breakdown=result.get("breakdown"), drift=clock,
        stalls=stalls(spans), long_spans=explained,
        span_totals=totals(spans, len(out["obs"].jobs)),
        module_s=d["module_s"],
        device_ops=sorted(d["op_s"].items(), key=lambda kv: -kv[1])[:40],
        cost=cost)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
