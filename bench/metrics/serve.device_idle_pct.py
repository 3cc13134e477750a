"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - (union of device op intervals) / window."""


def read(obs):
    d = obs.device
    if d is None or d["window_s"] <= 0 or d["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
