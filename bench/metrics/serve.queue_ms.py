"""Mean time an answered query waited in the engine's queue before its
batch was dispatched (``Completed.queued_ms``), in ms."""


def read(obs):
    q = getattr(obs, "queued_ms", None)
    if not q:
        return None
    return sum(q) / len(q)
