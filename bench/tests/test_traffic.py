"""The open-loop schedule and client: arrivals from the seed, and latency
timed from the due time, so a stalled client shows in later requests."""
import time
from concurrent.futures import Future

import numpy as np

from harness import traffic

STEADY = {"phases": [{"rate_qps": 200.0, "share": 1.0}]}
BURST = {"period_s": 5.0, "phases": [{"rate_qps": 400.0, "share": 0.2},
                                      {"rate_qps": 100.0, "share": 0.8}]}


def test_same_seed_same_arrivals_and_a_fixed_count():
    a = traffic.arrivals(STEADY, 3, 10.0)
    assert np.array_equal(a, traffic.arrivals(STEADY, 3, 10.0))
    b = traffic.arrivals(STEADY, 4, 10.0)
    assert len(a) == len(b) == 2000
    assert not np.array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 10.0
    # the same set of gaps in another order
    assert np.allclose(np.sort(np.diff(a)), np.sort(np.diff(b)), rtol=0.05,
                       atol=1e-4)


def test_burst_phases_carry_their_rates():
    a = traffic.arrivals(BURST, 9, 20.0)
    assert len(a) == round(4 * (400 * 1.0 + 100 * 4.0))
    phase = np.mod(a, 5.0)
    in_burst = np.sum(phase < 1.0)
    assert abs(in_burst - 1600) < 100
    assert abs((len(a) - in_burst) - 1600) < 100


def test_latency_counts_from_due_through_a_stall():
    due = np.arange(10) * 0.01

    def submit(i):
        if i == 3:
            time.sleep(0.2)         # the client stalls before request 3
        f = Future()
        f.set_result(i)
        return f

    client = traffic.OpenLoop(submit, lambda i: i, due)
    client.run(time.perf_counter())
    assert client.wait(1.0)
    lat, late = client.latency_s, client.late_s
    assert np.all(lat[:3] < 0.05)
    # requests due during the stall were sent late and count that wait
    assert np.all(lat[3:8] > 0.1)
    assert np.all(late[4:8] > 0.1)
    assert lat[4] > lat[7]
