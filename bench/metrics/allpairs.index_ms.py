"""Index build time per clustering: the program's ``index_build`` spans
(signatures, validity and the index's construction in
``SignatureIndex.build``) over the clusterings of the window, in ms."""


def read(obs):
    if not obs.jobs:
        return None
    spans = [s["dur"] for s in obs.spans if s["name"] == "index_build"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(obs.jobs)
