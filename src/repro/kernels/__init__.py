"""Pallas TPU kernels for the paper's compute hot-spots (validated with
interpret=True on CPU, compiled for v5e in tests/test_tpu_compile.py):

  hamming.py — the Signature Processor's blocked XOR+popcount sweep (the
               dense serving top-k)
  sw.py      — batched Smith-Waterman over a pair block: the anti-diagonal
               Gotoh sweep and the ungapped X-drop prefilter (the all-pairs
               tiler's inner loop), plus the legacy row wave
  spgemm.py  — upper-mask SpGEMM candidate emission for the self-join
  siggen.py  — the Signature Generator's fused score->threshold->hyperplane
               accumulation (two chained MXU matmuls per VMEM tile)

``hamming_count_kernel`` and ``siggen_accumulate_kernel`` have no caller on
a search path and do not compile for TPU (VMEM overflow; int32 matmul).

ops.py: jit'd public wrappers (padding + platform dispatch).
ref.py: oracles — the correctness contract for every kernel.
"""
