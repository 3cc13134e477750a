"""repro.obs: structured tracing, mergeable metrics, recompile sentinel.

The invariants pinned here:

* **one trace ID per query, end to end** — a query submitted to the
  async tier carries its ID from the ``submit`` instant through the
  dispatch batch, the router, the replica's serving spans, down to the
  ``resolve`` instant, with micro-batched queries sharing the batch's
  spans (honest attribution: the span names every query it served);
* **histograms merge exactly** — fixed-bucket merge is associative and
  equals the histogram of the concatenated samples, and quantiles stay
  within one bucket's relative width of the sample percentiles;
* **disabled tracing records nothing** — the serving path pays one
  branch, not a span;
* **the sentinel turns recompiles into assertions** — a warmed engine
  serves under ``expect_no_compiles``; a *fresh* ``Mesh`` over the same
  devices reuses every compiled ring (the PR 5 cache-key regression,
  now pinned at the sentinel layer); a changed static (probe cap) is a
  fresh program, never a silent recompile of the old one.
"""
import functools
import json
import warnings

import numpy as np
import pytest

from repro.core import LSHConfig
from repro.data import SyntheticProteinConfig, make_protein_sets
from repro.index import (QueryEngine, ServingConfig, ShardedIndex,
                         SignatureIndex)
from repro.obs import (REGISTRY, SENTINEL, TRACER, Histogram, Registry,
                       current_trace, default_bounds, span, trace_context,
                       trace_sentinel)
from repro.serve import AsyncEngine, ReplicaFleet
from repro.serve.metrics import Counters

CFG = LSHConfig(k=3, T=13, f=32, d=1)
SCFG = ServingConfig(k=5, max_batch=8, mode="probe")


@pytest.fixture(scope="module")
def data():
    return make_protein_sets(SyntheticProteinConfig(
        n_refs=120, n_homolog_queries=12, n_decoy_queries=12,
        ref_len_mean=90, ref_len_std=12, sub_rates=(0.04, 0.1), seed=77))


@pytest.fixture(scope="module")
def index(data):
    idx = SignatureIndex.build(CFG, data["ref_ids"], data["ref_lens"])
    idx._ensure_built()
    return idx


@pytest.fixture
def traced():
    """Tracing on with a clean buffer of the default size; always off +
    cleared afterwards."""
    TRACER.clear()
    TRACER.enable(capacity=65536)
    yield TRACER
    TRACER.disable()
    TRACER.clear()


# ---------------------------------------------------------------- histograms
def test_histogram_quantiles_track_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=20_000)
    h = Histogram()
    for v in samples:
        h.observe(float(v))
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum())
    for q in (0.50, 0.95, 0.99):
        want = float(np.percentile(samples, 100 * q))
        got = h.quantile(q)
        # one bucket's relative width (2**0.25 - 1 ~ 19%) is the bound
        assert abs(got - want) / want < 0.19, (q, got, want)


def test_histogram_merge_is_associative_and_exact():
    rng = np.random.default_rng(1)
    parts = [rng.lognormal(-4, 1, size=n) for n in (300, 1000, 50)]

    def hist(samples_list):
        h = Histogram()
        for s in samples_list:
            for v in s:
                h.observe(float(v))
        return h

    a, b, c = (hist([p]) for p in parts)
    left = hist([parts[0]]).merge(hist([parts[1]])).merge(hist([parts[2]]))
    right = hist([parts[0]]).merge(hist([parts[1]]).merge(hist([parts[2]])))
    whole = hist(parts)
    for other in (right, whole):
        np.testing.assert_array_equal(left.counts, other.counts)
        assert left.count == other.count
        assert left.sum == pytest.approx(other.sum)
    # unmerged inputs unchanged by being merge() arguments
    assert b.count == 1000 and c.count == 50
    with pytest.raises(ValueError):
        a.merge(Histogram(default_bounds(lo=1e-3)))


def test_histogram_state_roundtrip_merges():
    rng = np.random.default_rng(2)
    h = Histogram()
    for v in rng.lognormal(-4, 1, size=500):
        h.observe(float(v))
    # state() is what crosses a process boundary — must JSON-roundtrip
    rebuilt = Histogram.from_state(json.loads(json.dumps(h.state())))
    np.testing.assert_array_equal(rebuilt.counts, h.counts)
    assert rebuilt.quantile(0.95) == h.quantile(0.95)
    merged = Histogram().merge(h).merge(rebuilt)
    assert merged.count == 1000


# ---------------------------------------------------------------- registry
def test_registry_prometheus_exposition():
    reg = Registry()
    c = reg.counter("reqs_total", "requests", labelnames=("engine",))
    c.inc(engine="e0")
    c.inc(by=2, engine="e1")
    reg.gauge("depth").set(3)
    hf = reg.histogram("lat_seconds", "latency", labelnames=("engine",))
    hf.observe(0.010, engine="e0")
    hf.observe(0.020, engine="e0")
    text = reg.prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{engine="e0"} 1' in text
    assert 'reqs_total{engine="e1"} 2' in text
    assert "# TYPE depth gauge" in text and "depth 3" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{engine="e0",le="+Inf"} 2' in text
    assert 'lat_seconds_count{engine="e0"} 2' in text
    # cumulative bucket counts are monotonically non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 2
    # redeclaration with different type or labels is a bug, not a metric
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("reqs_total", labelnames=("replica",))
    assert reg.snapshot()["lat_seconds"]["engine=e0"]["count"] == 2


def test_family_merged_view():
    reg = Registry()
    hf = reg.histogram("h", labelnames=("replica",))
    for r, vals in (("r0", [0.01, 0.02]), ("r1", [0.03])):
        for v in vals:
            hf.observe(v, replica=r)
    assert hf.merged().count == 3


# ---------------------------------------------------------------- tracing
def test_disabled_tracing_records_nothing(index, data):
    assert not TRACER.enabled
    n0 = len(TRACER)
    with span("probe", B=4):
        pass
    eng = QueryEngine(index, SCFG, sharded=ShardedIndex(index))
    eng.query_batch(data["query_ids"][:2], data["query_lens"][:2])
    assert len(TRACER) == n0     # one branch, zero spans


def test_trace_context_tags_spans(traced):
    with trace_context((5, 6)):
        assert current_trace() == (5, 6)
        with span("probe", B=2):
            pass
    assert current_trace() == ()
    probes = [s for s in traced.spans() if s["name"] == "probe"]
    assert probes and probes[-1]["args"]["trace"] == [5, 6]
    assert probes[-1]["dur"] is not None


def test_trace_buffer_bounded(traced):
    import gc

    traced.enable(capacity=64)
    gc.disable()        # no collection's span among the 200
    try:
        for i in range(200):
            with span("x", i=i):
                pass
    finally:
        gc.enable()
    assert len(traced) == 64
    assert traced.chrome_trace()["otherData"]["dropped_spans"] == 136


def test_trace_id_propagation_end_to_end(index, data, traced, tmp_path):
    """Every submitted query's ID spans submit -> dispatch -> the serving
    spans of its batch -> resolve, on one timeline."""
    fleet = ReplicaFleet(index, SCFG, n_replicas=1, start_ingest=False)
    eng = AsyncEngine(fleet, start=False)
    rows = [np.asarray(data["query_ids"][j][:data["query_lens"][j]], np.int8)
            for j in range(3)]
    futs = [eng.submit(r) for r in rows]
    eng._drain_once()
    assert all(f.result(timeout=60).ok for f in futs)
    spans = traced.spans()
    submits = {s["args"]["trace"][0] for s in spans if s["name"] == "submit"}
    assert len(submits) == 3     # one fresh trace ID per query
    by_trace = {}
    for s in spans:
        for tid in s["args"].get("trace", ()):
            by_trace.setdefault(tid, set()).add(s["name"])
    for tid in submits:
        path = by_trace[tid]
        assert {"submit", "dispatch", "route", "query_batch",
                "probe", "resolve"} <= path, (tid, sorted(path))
    # micro-batching attribution is honest: the one dispatch span names
    # all three queries it served
    dispatch = [s for s in spans if s["name"] == "dispatch"]
    assert len(dispatch) == 1 and set(dispatch[0]["args"]["trace"]) == submits
    out = tmp_path / "trace.json"
    n = traced.export(out)
    obj = json.loads(out.read_text())
    assert n == len(obj["traceEvents"]) and n > 0
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "i", "M"} <= phases


def test_shed_resolves_with_reason(index, traced):
    fleet = ReplicaFleet(index, SCFG, n_replicas=1, start_ingest=False)
    eng = AsyncEngine(fleet, queue_depth=1, start=False)
    rows = [np.zeros(40, np.int8)] * 3
    futs = [eng.submit(r) for r in rows]
    outs = [f.result(timeout=5) for f in futs if f.done()]
    assert any(not o.ok and o.reason == "queue_full" for o in outs)
    sheds = [s for s in traced.spans() if s["name"] == "shed"]
    assert sheds and sheds[0]["args"]["reason"] == "queue_full"



# ------------------------------------------------- all-pairs and host spans
def test_all_pairs_spans_nest_inside_the_call(traced):
    """Index build, join, scoring and graph each get a span inside
    ``all_pairs_search``; the waves' fetches get ``drain`` spans."""
    import time

    from repro.allpairs import AllPairsConfig, WaveConfig, all_pairs_search
    from repro.data import FamilyCorpusConfig, make_family_corpus

    c = make_family_corpus(FamilyCorpusConfig(
        n_families=6, family_size=3, n_singletons=12, len_mean=80,
        len_std=10, sub_rate=0.04, seed=3))
    cfg = AllPairsConfig(lsh=CFG, wave=WaveConfig(prefilter=True))
    all_pairs_search(c["ids"], c["lens"], cfg)      # compile outside
    traced.clear()
    t0 = time.perf_counter() - traced._t0
    all_pairs_search(c["ids"], c["lens"], cfg)
    t1 = time.perf_counter() - traced._t0
    spans = traced.spans()
    top = {}
    for name in ("index_build", "emission", "score_pairs", "graph"):
        got = [sp for sp in spans if sp["name"] == name]
        assert len(got) == 1, name
        top[name] = got[0]
        assert t0 <= got[0]["ts"] and got[0]["ts"] + got[0]["dur"] <= t1
    assert top["index_build"]["cat"] == "lifecycle"
    order = sorted(top, key=lambda n: top[n]["ts"])
    assert order == ["index_build", "emission", "score_pairs", "graph"]
    assert sum(sp["dur"] for sp in top.values()) < t1 - t0
    sp = top["score_pairs"]
    waves = [w for w in spans if w["name"] == "wave"]
    drains = [d for d in spans if d["name"] == "drain"]
    assert len(waves) == len(drains) == sp["args"]["waves"] > 0
    assert {w["args"]["kind"] for w in waves} == \
        {d["args"]["kind"] for d in drains} == {"ungapped", "sw"}
    assert all({"B", "Lq", "Lr"} <= set(w["args"]) for w in waves)
    for x in waves + drains:
        assert sp["ts"] <= x["ts"] and \
            x["ts"] + x["dur"] <= sp["ts"] + sp["dur"]


def test_gc_collect_records_a_gc_span(traced):
    import gc

    gc.collect()
    got = [sp for sp in traced.spans() if sp["name"] == "gc"]
    assert got and got[-1]["cat"] == "runtime"
    assert got[-1]["args"]["generation"] == 2
    assert got[-1]["dur"] >= 0 and "collected" in got[-1]["args"]


def test_fresh_jit_records_a_lower_span(traced):
    import jax

    jax.jit(lambda x: x * 3 - 1)(np.arange(5, dtype=np.int32))
    got = [sp for sp in traced.spans() if sp["name"] == "lower"]
    assert got and all(sp["cat"] == "jit" for sp in got)
    assert {"trace", "lower"} <= {sp["args"]["event"] for sp in got}
    assert all(sp["dur"] >= 0 for sp in got)


def test_tracing_off_records_nothing_and_hooks_no_gc():
    import gc

    import jax

    TRACER.enable()
    TRACER.disable()
    TRACER.clear()
    assert TRACER._on_gc not in gc.callbacks
    gc.collect()
    jax.jit(lambda x: x + 7)(np.arange(3, dtype=np.int32))
    with span("probe", B=1):
        pass
    assert len(TRACER) == 0


def test_span_under_a_profiler_trace_opens_an_annotation(traced, tmp_path):
    """With the profiler running, a span also lands in the profile as a
    TraceMe event of its own name, on the profiler's clock."""
    import glob

    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("obs_test_annotated", cat="allpairs"):
            pass
    finally:
        jax.profiler.stop_trace()
    with span("obs_test_after", cat="allpairs"):    # no profiler: no event
        pass
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {ev.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "obs_test_annotated" in names
    assert "obs_test_after" not in names
    assert {sp["name"] for sp in traced.spans()} >= {"obs_test_annotated",
                                                     "obs_test_after"}


# ---------------------------------------------------------------- metrics glue
def test_counters_undeclared_bump_warns_but_counts():
    c = Counters("a")
    with pytest.warns(UserWarning, match="undeclared"):
        c.bump("typo")
    assert c["typo"] == 1        # back-compat: still counted
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        c.bump("a", by=2)        # declared names never warn
    assert c.snapshot() == {"a": 2, "typo": 1}


def test_engine_stats_bounded_and_resettable(index, data):
    eng = QueryEngine(index, SCFG, sharded=ShardedIndex(index))
    for _ in range(3):
        eng.query_batch(data["query_ids"][:4], data["query_lens"][:4])
    st = eng.stats()
    assert st["n_batches"] == 3 and st["n_queries"] == 12
    assert st["p50_ms"] > 0 and st["p99_ms"] >= st["p50_ms"]
    assert set(st["stage_ms"]) >= {"ladder", "sig", "probe"}
    eng.reset_stats()
    assert eng.stats()["n_batches"] == 0
    # the registry view is monotonic: reset never rewinds the scrape
    merged = REGISTRY.histogram("serve_batch_seconds",
                                labelnames=("engine",)).merged()
    assert merged.count >= 3


# ---------------------------------------------------------------- sentinel
def test_sentinel_counts_traces_not_calls():
    import jax

    site = "obs_test_traces"

    @jax.jit
    @trace_sentinel(site)
    def f(x):
        return x + 1

    f(np.ones(4, np.float32))
    f(np.zeros(4, np.float32))       # same shape: cached, no re-trace
    assert SENTINEL.total(site) == 1
    f(np.ones(8, np.float32))        # new shape: one fresh compile
    assert SENTINEL.total(site) == 2
    assert SENTINEL.recompiled() == {}
    assert SENTINEL.by_site()[site] == 2
    with pytest.raises(AssertionError, match="zero-compile"):
        with SENTINEL.expect_no_compiles(site, message="steady state"):
            f(np.ones(16, np.float32))
    with SENTINEL.expect_no_compiles(site):
        f(np.ones(16, np.float32))   # now warm: passes


def test_warmup_then_serving_is_compile_free(index, data):
    eng = QueryEngine(index, SCFG, sharded=ShardedIndex(index))
    n = eng.warmup(data["query_ids"], data["query_lens"])
    assert n > 0
    with SENTINEL.expect_no_compiles("ring",
                                     message="warmed sync engine"):
        for j in range(0, 12, 4):
            eng.query_batch(data["query_ids"][j:j + 4],
                            data["query_lens"][j:j + 4])


def test_fresh_mesh_does_not_recompile_ring(index, data):
    """The PR 5 regression, pinned at the sentinel layer: programs are
    cached by DEVICE TUPLE, so a freshly constructed (equal) Mesh and a
    fresh ShardedIndex reuse every compiled ring."""
    import jax
    from jax.sharding import Mesh

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng1 = QueryEngine(index, SCFG, sharded=ShardedIndex(index, mesh1))
    eng1.warmup(data["query_ids"], data["query_lens"])
    mesh2 = Mesh(np.array(jax.devices()[:1]), ("data",))   # fresh, equal
    eng2 = QueryEngine(index, SCFG, sharded=ShardedIndex(index, mesh2))
    with SENTINEL.expect_no_compiles("ring",
                                     message="fresh Mesh, same devices"):
        eng2.query_batch(data["query_ids"][:4], data["query_lens"][:4])
    # a changed static (probe cap) is a FRESH program — the sentinel must
    # see a new key, not a silent recompile of the old one
    before = SENTINEL.total("ring")
    cfg3 = ServingConfig(k=5, max_batch=8, mode="probe", probe_cap=64)
    eng3 = QueryEngine(index, cfg3, sharded=ShardedIndex(index, mesh1))
    eng3.query_batch(data["query_ids"][:4], data["query_lens"][:4])
    assert SENTINEL.total("ring") > before
    assert not {k: n for k, n in SENTINEL.recompiled().items()
                if k[0] == "ring"}, "cap growth misread as a recompile"


# ------------------------------------------- cross-process metric carrier
def test_registry_state_json_roundtrip_merges_exactly():
    """A worker snapshot survives json encode/decode and folds into a
    fresh parent registry exactly: counters add, gauges take the incoming
    value, histogram bucket counts add bucket-for-bucket."""
    from repro.obs import merge_registry_state, registry_state

    worker = Registry()
    worker.counter("pairs_total", "emitted pairs",
                   ("impl",)).labels(impl="spgemm").inc(7)
    worker.gauge("resident_rows", "rows").labels().set(128.0)
    h = worker.histogram("join_ms", "join latency", bounds=(1.0, 10.0))
    for v in (0.5, 3.0, 30.0):
        h.labels().observe(v)

    snap = json.loads(json.dumps(registry_state(worker)))
    parent = Registry()
    # the parent already saw some of the same traffic
    parent.counter("pairs_total", "emitted pairs",
                   ("impl",)).labels(impl="spgemm").inc(3)
    ph = parent.histogram("join_ms", "join latency", bounds=(1.0, 10.0))
    ph.labels().observe(5.0)
    merge_registry_state(snap, parent)
    merge_registry_state(snap, parent)       # associative: fold twice

    fams = parent.families()
    assert fams["pairs_total"].labels(impl="spgemm").value == 3 + 2 * 7
    assert fams["resident_rows"].labels().value == 128.0
    merged = ph.labels().state()
    # parent's one sample in (1,10] plus two copies of the worker's three
    assert merged["counts"] == [2, 3, 2]
    assert merged["count"] == 7


def test_merge_declares_missing_families():
    from repro.obs import merge_registry_state, registry_state

    worker = Registry()
    worker.histogram("only_in_worker_ms", "h", ("shard",),
                     bounds=(2.0,)).labels(shard="3").observe(1.0)
    parent = merge_registry_state(
        registry_state(worker), Registry())
    fam = parent.families()["only_in_worker_ms"]
    assert fam.bounds == (2.0,)
    assert fam.labels(shard="3").state()["count"] == 1


def test_merge_identity_drift_raises():
    """kind or labelname drift between worker and parent is a declaration
    bug and must raise, not silently fork the metric."""
    from repro.obs import merge_registry_state, registry_state

    worker = Registry()
    worker.counter("m", "as counter").labels().inc(1)
    parent = Registry()
    parent.gauge("m", "as gauge").labels().set(1.0)
    with pytest.raises(ValueError, match="redeclaration"):
        merge_registry_state(registry_state(worker), parent)

    worker2 = Registry()
    worker2.counter("n", "c", ("a",)).labels(a="x").inc(1)
    parent2 = Registry()
    parent2.counter("n", "c", ("b",)).labels(b="y").inc(1)
    with pytest.raises(ValueError, match="redeclaration"):
        merge_registry_state(registry_state(worker2), parent2)
