"""The trace reduction: busy union, idle gaps named by the host span open
in them, kernel time by module, on a synthetic trace and on a small one
recorded on the chip."""
import json
from pathlib import Path

import pytest

from harness import kernels, tracing

OPS = [("fusion.1", 0.10, 0.20), ("custom-call.2", 0.15, 0.30),
       ("fusion.1", 0.50, 0.60), ("copy.3", 0.90, 1.20)]
MODULES = [("jit_wave_scores_kernel(7)", 0.10, 0.30),
           ("jit__gather_wave(3)", 0.50, 0.60)]
SPANS = [{"name": "score_pairs", "ts": 0.0, "dur": 0.8, "args": {}},
         {"name": "wave", "ts": 0.35, "dur": 0.1,
          "args": {"kind": "sw", "B": 64, "Lq": 128, "Lr": 192}},
         {"name": "graph", "ts": 0.8, "dur": 0.05, "args": {}}]


def test_busy_union_and_gaps():
    assert tracing.union_seconds([(a, b) for _, a, b in OPS], 0.0, 1.0) \
        == pytest.approx(0.2 + 0.1 + 0.1)
    gaps = tracing.idle_gaps([(a, b) for _, a, b in OPS], 0.0, 1.0)
    assert gaps == [(0.0, 0.10), (0.30, 0.50), (0.60, 0.90)]


def test_reduce_names_gaps_by_innermost_span():
    r = tracing.reduce(OPS, MODULES, 0.0, 1.0, SPANS)
    assert r["busy_s"] == pytest.approx(0.4)
    assert r["window_s"] == 1.0
    assert r["module_s"]["jit_wave_scores_kernel"] == pytest.approx(0.2)
    gaps = dict((round(s, 6), n) for n, s in r["breakdown"]["idle_gaps"])
    assert gaps[0.3] == "score_pairs"        # 0.60-0.90: only score_pairs
    assert gaps[0.2] == "wave"               # 0.30-0.50: inside the wave
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(0.2)


def test_gcups_arithmetic():
    dev = tracing.reduce(OPS, MODULES, 0.0, 1.0, SPANS)
    cells = kernels.wave_cells(SPANS, "sw")
    assert cells == 64 * 128 * 192
    assert kernels.kernel_seconds(dev, "wave_scores_kernel") == \
        pytest.approx(0.2)
    assert kernels.wave_cells(SPANS, "ungapped") == 0


RECORDED = Path(__file__).parent / "data" / "trace_excerpt.json"


def test_reduce_recorded_chip_trace():
    """The first 0.25 s of an ``ecoli_cluster`` window traced on one v5e
    (device ops on the window's clock, op names shortened by
    ``op_base``): the reduction agrees with a plain sweep over it."""
    rec = json.loads(RECORDED.read_text())
    ops = [tuple(e) for e in rec["ops"]]
    hi = rec["window_s"]
    r = tracing.reduce(ops, [tuple(e) for e in rec["modules"]], 0.0, hi,
                       rec["spans"])
    # busy time by a plain walk over 1-microsecond cells
    cells = set()
    for _, a, b in ops:
        cells.update(range(int(max(a, 0) * 1e6), int(min(b, hi) * 1e6)))
    assert r["busy_s"] == pytest.approx(len(cells) * 1e-6, abs=len(ops) * 2e-6)
    assert 0 < r["busy_s"] <= r["window_s"] == hi
    assert sum(s for _, s in r["breakdown"]["idle_gaps"]) <= hi - r["busy_s"] + 1e-9
    top = r["breakdown"]["device_ops"]
    assert len(top) == 10 and all(" " not in n for n, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
