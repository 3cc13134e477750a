"""Drain time per clustering: the program's ``drain`` spans, each the
blocking fetch of one wave's scores to the host (the host waits there for
the device), over the clusterings of the window, in ms."""


def read(obs):
    if not obs.jobs:
        return None
    spans = [s["dur"] for s in obs.spans if s["name"] == "drain"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(obs.jobs)
