"""Mean number of queries per served batch: ``B`` of the program's
``query_batch`` spans in the window."""


def read(obs):
    b = [s["args"]["B"] for s in obs.spans if s["name"] == "query_batch"]
    if not b:
        return None
    return sum(b) / len(b)
