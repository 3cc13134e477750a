"""Graph time per clustering: the program's ``graph`` spans (edge
threshold, union-find, families in ``cluster_families``) over the
clusterings of the window, in ms."""


def read(obs):
    if not obs.jobs:
        return None
    spans = [s["dur"] for s in obs.spans if s["name"] == "graph"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(obs.jobs)
