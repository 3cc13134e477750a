"""Pallas TPU kernels for the paper's compute hot-spots (validated with
interpret=True on CPU, compiled for v5e in tests/test_tpu_compile.py):

  hamming.py — the Signature Processor's blocked XOR+popcount sweep (the
               dense serving top-k)
  sw.py      — batched Smith-Waterman over a pair block: the anti-diagonal
               Gotoh sweep, its percent-identity mode and the ungapped
               X-drop prefilter (the all-pairs tiler's inner loop)
  spgemm.py  — upper-mask SpGEMM candidate emission for the self-join

ops.py: jit'd public wrappers (padding + platform dispatch).
ref.py: oracles — the correctness contract for every kernel.
"""
