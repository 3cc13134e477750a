"""Self-join time per clustering: the program's ``emission`` spans (the
band layout's keyed SpGEMM join, pack included) over the clusterings of
the window, in ms."""


def read(obs):
    if not obs.jobs:
        return None
    spans = [s["dur"] for s in obs.spans if s["name"] == "emission"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(obs.jobs)
