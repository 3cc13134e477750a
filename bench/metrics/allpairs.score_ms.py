"""Scoring time per clustering: the program's ``score_pairs`` spans
(device gather, Pallas prefilter and DP waves, drain) over the clusterings
of the window, in ms."""


def read(obs):
    if not obs.jobs:
        return None
    spans = [s["dur"] for s in obs.spans if s["name"] == "score_pairs"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(obs.jobs)
