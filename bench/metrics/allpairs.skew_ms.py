"""Device time per clustering of the two wave kernel programs outside
their Pallas kernels: the ``module_s`` of ``jit_ungapped_scores_kernel``
and ``jit_wave_scores_kernel`` less the ``op_s`` of the kernels' own ops,
which the program names ``ungapped_prefilter`` and ``wavefront_dp`` (XLA
adds a ``.<n>`` suffix: ``ungapped_prefilter.1`` on a v5e). What is left
is the skew (gather, selects, pad) that builds each kernel's input, in
ms. A program whose kernels carry no such name gives nothing."""
import re

MODULES = ("jit_ungapped_scores_kernel", "jit_wave_scores_kernel")
KERNEL_OP = re.compile(r"(ungapped_prefilter|wavefront_dp)(\.\d+)?")


def read(obs):
    d = obs.device
    if d is None or not obs.jobs:
        return None
    module = sum(d["module_s"].get(m, 0.0) for m in MODULES)
    kernel = sum(s for op, s in d["op_s"].items()
                 if KERNEL_OP.fullmatch(op))
    if module <= 0 or kernel <= 0:
        return None
    return 1e3 * (module - kernel) / len(obs.jobs)
