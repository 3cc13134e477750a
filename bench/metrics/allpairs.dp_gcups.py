"""Smith-Waterman DP rate in GCUPS: padded cell updates of every DP wave
of the window (B * Lq * Lr of each ``wave`` span of kind ``sw``) over the
device time of the wavefront kernel's programs in the profiler trace."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.kernels import wave_cells, kernel_seconds  # noqa: E402


def read(obs):
    secs = kernel_seconds(obs.device, "wave_scores_kernel")
    cells = wave_cells(obs.spans, "sw")
    if not secs or not cells:
        return None
    return cells / secs / 1e9
