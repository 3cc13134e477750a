"""Work and device time of the DP kernels, shared by their readers.

A wave of B pairs padded to (Lq, Lr) updates B * Lq * Lr DP cells, the
unit of GCUPS (giga cell updates per second), the field's own rate for
Smith-Waterman. Device time is that of the XLA programs the kernel's jitted
entry compiles to (``jit_<kernel>``), whose device events the trace
reduction sums by module name.
"""
from __future__ import annotations


def wave_cells(spans, kind: str) -> int:
    """Padded cell updates of the program's ``wave`` spans of ``kind``."""
    return sum(int(s["args"]["B"]) * int(s["args"]["Lq"]) * int(s["args"]["Lr"])
               for s in spans
               if s["name"] == "wave" and s["args"].get("kind") == kind)


def kernel_seconds(device, kernel: str) -> float:
    """Summed device time of ``jit_<kernel>`` programs in the trace."""
    if device is None:
        return 0.0
    return sum(v for k, v in device["module_s"].items()
               if k == f"jit_{kernel}")
