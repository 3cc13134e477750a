"""Mean query-signature time per served batch: the program's ``sig``
spans in the window, in ms."""


def read(obs):
    d = [s["dur"] for s in obs.spans if s["name"] == "sig"]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
