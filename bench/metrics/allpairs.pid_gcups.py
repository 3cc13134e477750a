"""PID wavefront rate in GCUPS: padded cell updates of every PID wave of
the window (B * Lq * Lr of each ``wave`` span of kind ``pid``) over the
device time of the PID kernel's programs (``jit_wave_pid_kernel``, skew
included) in the profiler trace. A program without that kernel (the row
wave and host walk) gives nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.kernels import wave_cells, kernel_seconds  # noqa: E402


def read(obs):
    secs = kernel_seconds(obs.device, "wave_pid_kernel")
    cells = wave_cells(obs.spans, "pid")
    if not secs or not cells:
        return None
    return cells / secs / 1e9
