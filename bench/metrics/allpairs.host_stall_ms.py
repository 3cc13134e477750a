"""Host stalls per clustering: the union of the program's ``gc`` spans
(Python garbage collections) and ``lower`` spans (JAX traces, lowerings,
backend compiles and persistent-cache loads) inside the window's jobs,
over the clusterings of the window, in ms."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.tracing import union_seconds  # noqa: E402


def read(obs):
    if not obs.jobs:
        return None
    stalls = [(s["ts"], s["ts"] + s["dur"]) for s in obs.spans
              if s["name"] in ("gc", "lower") and s["dur"] is not None]
    if not stalls:
        return None
    lo, hi = obs.jobs[0][0], obs.jobs[-1][1]
    return 1e3 * union_seconds(stalls, lo, hi) / len(obs.jobs)
