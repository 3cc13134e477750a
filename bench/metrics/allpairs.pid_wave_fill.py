"""How full the PID waves run: the real pairs over the padded batch
lanes, ``100 * sum(n) / sum(B)`` over the window's ``wave`` spans of kind
``pid``, in %. The PID kernel and its skew work in proportion to B, so
the rest of each wave is padding."""


def read(obs):
    waves = [s["args"] for s in obs.spans
             if s["name"] == "wave" and s["args"].get("kind") == "pid"]
    lanes = sum(a["B"] for a in waves)
    if not lanes:
        return None
    return 100.0 * sum(a["n"] for a in waves) / lanes
