"""The reader of the wave-fill metric, on synthetic spans."""
import pytest

from harness.common import load_reader


class Obs:
    def __init__(self, spans, jobs, device=None):
        self.spans, self.jobs, self.device = spans, jobs, device


def sp(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


JOBS = [(0.0, 1.0), (1.0, 2.0)]
SPANS = [sp("index_build", 0.00, 0.10), sp("score_pairs", 0.12, 0.80),
         sp("drain", 0.30, 0.05, B=64, kind="sw"), sp("graph", 0.92, 0.01)]


def test_wave_fill_is_real_pairs_over_lanes_of_score_waves():
    """Prefilter and DP waves count; a PID wave and a span of another
    name that carry ``n`` and ``B`` do not."""
    spans = SPANS + [
        sp("wave", 0.20, 0.01, kind="ungapped", B=256, n=256, Lq=320,
           Lr=320),
        sp("wave", 0.21, 0.01, kind="ungapped", B=256, n=40, Lq=320,
           Lr=384),
        sp("wave", 0.40, 0.01, kind="sw", B=64, n=8, Lq=320, Lr=320),
        sp("wave", 0.41, 0.01, kind="pid", B=64, n=1, Lq=64, Lr=64),
        sp("host_gather", 0.42, 0.01, B=64, n=1)]
    got = load_reader("allpairs.wave_fill")(Obs(spans, JOBS))
    assert got == pytest.approx(100 * (256 + 40 + 8) / (256 + 256 + 64))


def test_wave_fill_without_waves_gives_nothing():
    assert load_reader("allpairs.wave_fill")(Obs(SPANS, JOBS)) is None
    pid = [sp("wave", 0.41, 0.01, kind="pid", B=64, n=1, Lq=64, Lr=64)]
    assert load_reader("allpairs.wave_fill")(Obs(pid, JOBS)) is None
