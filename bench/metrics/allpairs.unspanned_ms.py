"""Time per clustering outside the join and scoring spans: index build
(signatures, validity, bucketing) and the host graph, which the program
does not span yet. The benchmark's own job time less ``emission`` and
``score_pairs``, in ms."""


def read(obs):
    if not obs.jobs or not obs.spans:
        return None
    total = sum(b - a for a, b in obs.jobs)
    inner = sum(s["dur"] for s in obs.spans
                if s["name"] in ("emission", "score_pairs"))
    return 1e3 * (total - inner) / len(obs.jobs)
