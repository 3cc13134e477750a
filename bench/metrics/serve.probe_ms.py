"""Mean probe time per served batch (band keys, ring probe, top-k, copy
to host): the program's ``probe`` spans in the window, in ms."""


def read(obs):
    d = [s["dur"] for s in obs.spans if s["name"] == "probe"]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
