"""The percent-identity clustering cell on the CPU: its two readers on
synthetic spans and a synthetic reduced trace, the reference's walk
against the published rule, a run ``correct`` against the reference, a
broken PID caught, and the bfloat16 control rejected."""
import time

import numpy as np
import pytest

import run as bench_run
from harness import allpairs_pid, reference_pid, tracing
from harness.common import BENCH_DIR, ROOT, Cell, CompileCounter, read_json
from harness.common import load_reader

BENCH = read_json(ROOT / "BENCHMARK.json")
DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "ecoli_pid_cluster"


class Obs:
    def __init__(self, spans, jobs, device=None):
        self.spans, self.jobs, self.device = spans, jobs, device


def sp(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


JOBS = [(0.0, 1.0), (1.0, 2.0)]
WAVES = [sp("wave", 0.20, 0.01, kind="pid", B=64, n=64, Lq=320, Lr=320),
         sp("wave", 0.21, 0.01, kind="pid", B=16, n=3, Lq=320, Lr=704),
         sp("drain", 0.22, 0.05, B=64, kind="pid"),
         sp("wave", 0.40, 0.01, kind="sw", B=64, n=8, Lq=64, Lr=64),
         sp("wave", 0.41, 0.01, kind="ungapped", B=256, n=1, Lq=64,
            Lr=64)]
# a v5e names the PID kernel's op after its pallas_call (``wavefront_pid``)
# and its program after the jitted entry (``jit_wave_pid_kernel``)
OPS = [("fusion", 0.00, 0.10), ("wavefront_pid.1", 0.10, 0.50),
       ("wavefront_dp.1", 0.60, 0.70)]
MODULES = [("jit_wave_pid_kernel(7)", 0.00, 0.50),
           ("jit_wave_scores_kernel(12)", 0.60, 0.70)]


def test_pid_wave_fill_counts_pid_waves_only():
    got = load_reader("allpairs.pid_wave_fill")(Obs(WAVES, JOBS))
    assert got == pytest.approx(100 * (64 + 3) / (64 + 16))
    other = [s for s in WAVES if s["args"].get("kind") != "pid"]
    assert load_reader("allpairs.pid_wave_fill")(Obs(other, JOBS)) is None


def test_pid_gcups_is_pid_cells_over_pid_program_time():
    dev = tracing.reduce(OPS, MODULES, 0.0, 1.0, [])
    got = load_reader("allpairs.pid_gcups")(Obs(WAVES, JOBS, dev))
    cells = 64 * 320 * 320 + 16 * 320 * 704
    assert got == pytest.approx(cells / 0.50 / 1e9)


def test_pid_gcups_without_the_pid_kernel_gives_nothing():
    """The host route (row wave and walk) has PID waves but no PID
    kernel program; a trace without a device gives nothing either."""
    dev = tracing.reduce(OPS[2:], MODULES[1:], 0.0, 1.0, [])
    assert load_reader("allpairs.pid_gcups")(Obs(WAVES, JOBS, dev)) is None
    assert load_reader("allpairs.pid_gcups")(Obs(WAVES, JOBS, None)) is None


def test_reference_walk_by_hand():
    """q = WAW, r = WCW: H's best is the whole diagonal (11 - 0 + 11 =
    22 through the A/C mismatch, which BLOSUM62 scores 0): length 3, two
    identities."""
    enc = {c: i for i, c in enumerate(reference_pid.reference.AMINO_ACIDS)}
    q = np.array([[enc[c] for c in "WAW"]], np.int8)
    r = np.array([[enc[c] for c in "WCW"]], np.int8)
    H = np.asarray(reference_pid._h_block(q, r, np.array([3]),
                                          np.array([3]), gap=-4, low=False))
    assert H.shape == (1, 4, 4) and H.max() == 22
    score, ident, length = reference_pid.walk(H, q, r, -4)
    assert (score[0], ident[0], length[0]) == (22, 2, 3)


@pytest.fixture(scope="module")
def counter():
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_out" / "test_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CompileCounter()


def small_pid():
    cfg = read_json(BENCH_DIR / "configs" / "nc000913_pid.json")
    cfg["corpus"] = dict(cfg["corpus"], n=300, len_mean=90, len_std=15)
    return cfg


def cell(cfg, seed, tmp_path):
    return Cell(CELL, cfg, {}, seed=seed, seconds=1.0, trace=False,
                scratch=tmp_path, t_process=time.perf_counter())


@pytest.mark.parametrize("on_tpu", [False, True], ids=["host", "kernel"])
def test_pid_run_is_correct(counter, tmp_path, monkeypatch, on_tpu):
    """Both routes, the chip's (the Pallas PID kernel, interpreted here)
    and the host's, clustering a small corpus."""
    import repro.allpairs.tiles as tiles
    monkeypatch.setattr(tiles, "on_tpu", lambda: on_tpu)
    out = allpairs_pid.run(cell(small_pid(), 2**32 + 15, tmp_path), counter)
    res = bench_run.result_line(BENCH, CELL, out, DEV, False)
    assert res["correct"], res["checks"]
    assert out["notes"]["pairs"] > 0 and out["notes"]["edges"] > 0
    assert set(res["metrics"]) == {"cluster_s", "setup_s"}
    assert set(res["checks"]) >= {"pid_diff", "aln_len_diff", "dp_diff",
                                  "pair_set_diff", "family_diff"}


def _broken_host_walk(monkeypatch):
    """The host walk, counting positive scores as identities."""
    import repro.align.smith_waterman as sw

    def broken(H, q, r, sub):
        i, j = np.unravel_index(np.argmax(H), H.shape)
        ident = length = 0
        while i > 0 and j > 0 and H[i, j] > 0:
            if H[i, j] == H[i - 1, j - 1] + sub[i - 1, j - 1]:
                ident += int(sub[i - 1, j - 1] > 0)
                i, j = i - 1, j - 1
            elif H[i, j] == H[i - 1, j] + sw.GAP:
                i -= 1
            else:
                j -= 1
            length += 1
        return 100.0 * ident / max(length, 1), length

    monkeypatch.setattr(sw, "_traceback_pid", broken)


def _broken_kernel(monkeypatch):
    """The chip's route (the Pallas PID kernel, interpreted here), its
    match bit set for every positive score."""
    import jax
    import jax.numpy as jnp
    import repro.allpairs.tiles as tiles
    from repro.kernels import ops, sw

    base = sw._BSENT.astype(np.int32)
    table = np.where(base == sw.SENT8, sw.SENT8,
                     2 * base + (base > 0)).astype(np.int8)

    def kernel(qs, rs, *, bb, interpret):
        best, walk = sw._wave_call(sw._skewed(qs, rs, table), mode="pid",
                                   bb=bb, interpret=interpret,
                                   gap_open=sw.GAP)
        return jnp.concatenate([best, walk >> 16, walk & 0xFFFF], axis=1)

    monkeypatch.setattr(tiles, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "wave_pid_kernel",
                        jax.jit(kernel, static_argnames=("bb", "interpret")))


@pytest.mark.parametrize("breaks", [_broken_host_walk, _broken_kernel],
                         ids=["host_walk", "kernel"])
def test_pid_counted_from_positive_scores_is_caught(counter, tmp_path,
                                                    monkeypatch, breaks):
    """A PID that counts every positively scoring diagonal step as an
    identity (I/V, F/Y score 3) makes ``correct`` false, on either
    route."""
    breaks(monkeypatch)
    out = allpairs_pid.run(cell(small_pid(), 16, tmp_path), counter)
    res = bench_run.result_line(BENCH, CELL, out, DEV, False)
    assert not res["correct"]
    assert res["checks"]["pid_diff"]["value"] > 0
    assert res["checks"]["aln_len_diff"]["value"] == 0


def test_pid_control_is_rejected():
    cfg = read_json(BENCH_DIR / "configs" / "nc000913_pid.json")
    cfg["corpus"] = dict(cfg["corpus"], n=240)     # full lengths, fewer rows
    got = allpairs_pid.control(cfg, 11)
    assert max(got.values()) > 0, got
