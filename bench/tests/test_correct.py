"""The comparison that decides ``correct``, at a size a test run holds:
the program's timed path agrees with the plain reference, the control
(the reference in bfloat16) is rejected, and a run whose timed path is
broken underneath reports ``correct`` false."""
import json
import time

import numpy as np
import pytest

import control
import run as bench_run
from harness import allpairs, reference, serve
from harness.common import BENCH_DIR, ROOT, Cell, CompileCounter, read_json

BENCH = read_json(ROOT / "BENCHMARK.json")
# the serving cells' metrics, for the serving driver (its cells are not in
# BENCHMARK.json yet; see PERF.md)
SERVE_BENCH = {"end_to_end": [
    {"name": n, "unit": u} for n, u in (
        ("search_p95_ms", "ms"), ("search_p50_ms", "ms"),
        ("search_goodput_qps", "queries/s"), ("setup_s", "s"))],
    "per_layer": []}
DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def counter():
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_out" / "test_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CompileCounter()


def small_allpairs():
    cfg = read_json(BENCH_DIR / "configs" / "nc000913_allpairs.json")
    cfg["corpus"] = dict(cfg["corpus"], n=300, len_mean=90, len_std=15)
    return cfg


def small_serve():
    cfg = read_json(BENCH_DIR / "configs" / "swissprot_serve.json")
    cfg["refs"] = dict(cfg["refs"], n_refs=2000, len_mean=90, len_std=15)
    cfg["max_batch"] = 8
    tr = read_json(BENCH_DIR / "traffic" / "steady.json")
    tr.update(pool=64, phases=[{"rate_qps": 40.0, "share": 1.0}])
    return cfg, tr


def cell(name, cfg, tr, seed, tmp_path):
    return Cell(name, cfg, tr, seed=seed, seconds=1.0, trace=False,
                scratch=tmp_path, t_process=time.perf_counter())


def test_allpairs_run_is_correct(counter, tmp_path):
    out = allpairs.run(cell("ecoli_cluster", small_allpairs(), {}, 2**32 + 5,
                            tmp_path), counter)
    res = bench_run.result_line(BENCH, "ecoli_cluster", out, DEV, False)
    assert res["correct"], res["checks"]
    assert out["notes"]["pairs"] > 0 and out["notes"]["edges"] > 0
    assert set(res["metrics"]) == {"cluster_s", "setup_s"}
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_serve_run_is_correct(counter, tmp_path):
    cfg, tr = small_serve()
    out = serve.run(cell("swissprot_search_steady", cfg, tr, 2**32 + 6,
                         tmp_path), counter)
    res = bench_run.result_line(SERVE_BENCH, "swissprot_search_steady", out,
                                DEV, False)
    assert res["correct"], res["checks"]
    assert out["notes"]["answers_compared"] == out["attempted"] > 0
    assert set(res["metrics"]) == {"search_p50_ms", "search_p95_ms",
                                   "search_goodput_qps", "setup_s"}


def test_allpairs_control_is_rejected():
    cfg = read_json(BENCH_DIR / "configs" / "nc000913_allpairs.json")
    cfg["corpus"] = dict(cfg["corpus"], n=240)     # full lengths, fewer rows
    got = control.allpairs_control(cfg, 11)
    assert max(got.values()) > 0, got


def test_serve_control_is_rejected():
    cfg, tr = small_serve()
    cfg["refs"] = dict(cfg["refs"], len_mean=373, len_std=80)
    got = control.serve_control(cfg, tr, 12, 2.0)
    assert got["wrong_answers"] > 0, got


def test_allpairs_altered_score_is_caught(counter, tmp_path, monkeypatch):
    import repro.allpairs as ap

    real = ap.score_pairs

    def altered(*a, **kw):
        sc = real(*a, **kw)
        kept = np.flatnonzero(sc.kept)
        sc.scores[kept[0]] += 1         # one DP answer altered
        return sc

    monkeypatch.setattr(ap, "score_pairs", altered)
    out = allpairs.run(cell("ecoli_cluster", small_allpairs(), {}, 7,
                            tmp_path), counter)
    res = bench_run.result_line(BENCH, "ecoli_cluster", out, DEV, False)
    assert not res["correct"]
    assert res["checks"]["dp_diff"]["value"] > 0


def test_allpairs_dropped_pair_is_caught(counter, tmp_path, monkeypatch):
    import repro.allpairs as ap

    real = ap.lsh_self_join

    def dropped(*a, **kw):
        j = real(*a, **kw)
        object.__setattr__(j, "pairs", j.pairs[1:])
        return j

    monkeypatch.setattr(ap, "lsh_self_join", dropped)
    out = allpairs.run(cell("ecoli_cluster", small_allpairs(), {}, 8,
                            tmp_path), counter)
    res = bench_run.result_line(BENCH, "ecoli_cluster", out, DEV, False)
    assert not res["correct"]
    assert res["checks"]["pair_set_diff"]["value"] > 0


def test_serve_altered_answer_is_caught(counter, tmp_path, monkeypatch):
    from repro.index.service import QueryEngine

    real = QueryEngine.query_batch

    def altered(self, ids, lens):
        nid, nd = real(self, ids, lens)
        nid = nid.copy()
        nid[0, 0] = nid[0, 0] + 1 if nid[0, 0] >= 0 else 0
        return nid, nd

    cfg, tr = small_serve()
    monkeypatch.setattr(QueryEngine, "query_batch", altered)
    out = serve.run(cell("swissprot_search_steady", cfg, tr, 9, tmp_path),
                    counter)
    res = bench_run.result_line(SERVE_BENCH, "swissprot_search_steady", out,
                                DEV, False)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_reference_signatures_match_published_hash():
    # Java String.hashCode("ARN") = ((65*31)+82)*31+78
    bits = reference.hash_bits(3, 32, "java")
    h = ((65 * 31) + 82) * 31 + 78
    wid = 0 * 400 + 1 * 20 + 2          # A R N
    assert int(sum(int(b) << i for i, b in enumerate(bits[wid]))) == h
