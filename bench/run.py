#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name: the cell in
``BENCHMARK.json``, the configuration in the file that names, the traffic
in ``bench/traffic/<traffic>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``. The configuration's ``operation`` picks the
driver (``bench/harness/<operation>.py``). A run sets up and warms the
system, measures for ``--seconds``, then checks every answer of the window
against a plain reference. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from the program's spans and
a profiler trace of the window. The last stdout line is one JSON object;
the numbers compared for ``correct`` close both stdout's line and stderr.
A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _applies(entry: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def result_line(bench: dict, cell: str, out: dict, dev: dict,
                trace: bool) -> dict:
    """The run's result object from a driver's output: the cell's
    end-to-end metrics (``trace`` off) or per-layer metrics (on), the
    device, and the numbers compared for ``correct``, last."""
    from harness.common import load_reader
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    metrics = {}
    if trace:
        reported = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if _applies(m, cell, reported):
                v = load_reader(m["name"])(out["obs"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    checks = out["checks"]
    device = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    tr = out["obs"].device
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(ROOT / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    wl = next((w for w in bench["workloads"] if w["name"] == args.workload),
              None)
    if wl is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(ROOT / conf["file"]) as fh:
        config = json.load(fh)
    with open(BENCH_DIR / "traffic" / f"{wl['traffic']}.json") as fh:
        traffic = json.load(fh)

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from harness.common import (Cell, CompileCounter, device_info,
                                use_compile_cache)
    use_compile_cache()     # before anything compiles
    try:
        import repro  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        return 4
    counter = CompileCounter()

    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < wl["chips"]:
        print(f"needs {wl['chips']} TPU chip(s); JAX found {dev['count']} "
              f"{dev['platform']} device(s)", file=sys.stderr)
        return 3
    with open(BENCH_DIR / "peaks.json") as fh:
        peaks = json.load(fh)["devices"]
    if dev["kind"] not in peaks:
        print(f"device kind {dev['kind']!r} is not in bench/peaks.json",
              file=sys.stderr)
        return 3
    print(f"[device] {dev['platform']} {dev['kind']} x {dev['count']}",
          file=sys.stderr, flush=True)

    cell = Cell(name=wl["name"], config=config, traffic=traffic,
                seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                chips=wl["chips"], scratch=ROOT / ".bench_out" / wl["name"],
                t_process=T_PROCESS)
    cell.scratch.mkdir(parents=True, exist_ok=True)
    driver = importlib.import_module(f"harness.{config['operation']}")
    out = driver.run(cell, counter)

    result = result_line(bench, wl["name"], out, dev, bool(args.trace))
    checks = out["checks"]
    tr = out["obs"].device
    print("[notes] " + json.dumps(out["notes"]), file=sys.stderr)
    if tr is not None:
        print(f"[trace] {tr['trace_bytes']} bytes; modules "
              + json.dumps(dict(sorted(tr['module_s'].items(),
                                       key=lambda kv: -kv[1])[:12])),
              file=sys.stderr)
    for name, v, lim in checks:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
