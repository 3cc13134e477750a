"""The readers of the all-pairs index, graph, drain, host-stall and skew
metrics, on synthetic spans and a synthetic reduced device trace."""
import pytest

from harness import tracing
from harness.common import load_reader


class Obs:
    def __init__(self, spans, jobs, device=None):
        self.spans, self.jobs, self.device = spans, jobs, device


def sp(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


JOBS = [(0.0, 1.0), (1.0, 2.0)]
SPANS = [sp("index_build", 0.00, 0.10), sp("emission", 0.10, 0.02),
         sp("score_pairs", 0.12, 0.80), sp("drain", 0.30, 0.05, B=64,
                                             kind="sw"),
         sp("drain", 0.50, 0.15, B=256, kind="ungapped"),
         sp("graph", 0.92, 0.01),
         sp("index_build", 1.00, 0.12), sp("score_pairs", 1.12, 0.80),
         sp("graph", 1.92, 0.03),
         # host stalls: a lowering inside index_build, a gc inside it
         # (their union counts once), one before the window's first job
         sp("lower", 0.01, 0.04, event="cache_load"),
         sp("gc", 0.03, 0.04, generation=2, collected=10),
         sp("lower", -0.50, 0.20, event="compile"),
         sp("gc", 1.50, 0.01, generation=0, collected=0)]


@pytest.mark.parametrize("name, want", [
    ("allpairs.index_ms", 1e3 * (0.10 + 0.12) / 2),
    ("allpairs.graph_ms", 1e3 * (0.01 + 0.03) / 2),
    ("allpairs.drain_ms", 1e3 * (0.05 + 0.15) / 2),
    # [0.01, 0.07) and [1.50, 1.51); the lowering before the jobs is out
    ("allpairs.host_stall_ms", 1e3 * (0.06 + 0.01) / 2),
])
def test_span_readers_per_clustering(name, want):
    assert load_reader(name)(Obs(SPANS, JOBS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["allpairs.index_ms", "allpairs.graph_ms",
                                  "allpairs.drain_ms",
                                  "allpairs.host_stall_ms"])
def test_span_readers_without_their_spans_give_nothing(name):
    """A program that lacks the span (the parent of the PR that added
    it) reads as no value, not as zero or an error."""
    old = [s for s in SPANS if s["name"] in ("emission", "score_pairs")]
    assert load_reader(name)(Obs(old, JOBS)) is None
    assert load_reader(name)(Obs(SPANS, [])) is None


# Device ops as a v5e names them: XLA calls each Pallas kernel's custom
# call after the pallas_call's name, with a ".<n>" suffix; everything else
# in the two kernel programs (the skew's gather fusion, its select fusion,
# the sentinel pad) keeps XLA's own names.
OPS = [("fusion", 0.00, 0.10), ("bitcast_select_fusion.19", 0.10, 0.25),
       ("pad.1", 0.25, 0.27), ("ungapped_prefilter.1", 0.27, 0.57),
       ("fusion.20", 0.60, 0.70), ("wavefront_dp.1", 0.70, 0.80),
       ("gather_fusion", 0.85, 0.90)]
MODULES = [("jit_ungapped_scores_kernel(11)", 0.00, 0.57),
           ("jit_wave_scores_kernel(12)", 0.60, 0.80),
           ("jit__gather_wave(3)", 0.85, 0.90)]


def test_skew_is_kernel_programs_less_named_kernel_ops():
    dev = tracing.reduce(OPS, MODULES, 0.0, 1.0, [])
    got = load_reader("allpairs.skew_ms")(Obs([], JOBS, dev))
    # (0.57 + 0.20) of the two programs less (0.30 + 0.10) of the kernels
    assert got == pytest.approx(1e3 * (0.77 - 0.40) / 2)


def test_skew_needs_the_named_kernels():
    """The ops of a program whose ``pallas_call``s carry no name (the
    kernel op takes the jit's name) are not taken for the kernels."""
    unnamed = [(n.replace("ungapped_prefilter", "ungapped_scores_kernel")
                .replace("wavefront_dp", "wave_scores_kernel"), a, b)
               for n, a, b in OPS]
    dev = tracing.reduce(unnamed, MODULES, 0.0, 1.0, [])
    assert load_reader("allpairs.skew_ms")(Obs([], JOBS, dev)) is None
    assert load_reader("allpairs.skew_ms")(Obs([], JOBS, None)) is None
