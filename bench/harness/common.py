"""What every driver shares: the cell it runs, the compile counter, the
device's identity and memory, and the per-layer metric readers."""
from __future__ import annotations

import importlib.util
import json
import threading
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``.jax_cache/`` of the checkout (a stable
    path, so the next run finds it). Every program is stored, also the
    sub-second wave programs that JAX's default threshold skips."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclass
class Cell:
    """One run of one workload: its configuration and traffic files, the
    run's seed, length and trace flag, and where it may write."""
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    scratch: Path = field(default_factory=lambda: ROOT / ".bench_out")
    t_process: float = 0.0      # perf_counter at process start


class CompileCounter:
    """Counts JAX's compile events and the program's sentinel traces.

    ``compiles`` are programs the XLA compiler built: backend compile
    requests less persistent-cache hits. ``lowerings`` also counts
    programs traced and lowered again but served from the cache."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: dict[str, int] = {}
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        with self._lock:
            self.events[name] = self.events.get(name, 0) + 1

    def _on_duration(self, name, _secs, **_kw):
        self._on_event(name)

    def snapshot(self) -> dict:
        from repro.obs import SENTINEL
        with self._lock:
            ev = dict(self.events)
        backend = ev.get("/jax/core/compile/backend_compile_duration", 0)
        hits = ev.get("/jax/compilation_cache/cache_hits", 0)
        return dict(compiles=backend - hits, cache_hits=hits,
                    lowerings=ev.get(
                        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0),
                    sentinel=SENTINEL.total())

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def memory_peak_bytes(n_chips: int) -> int | None:
    """Peak bytes in use on the fullest of the chips the cell used."""
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``'s
    ``read(obs) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


class ProgramSpans:
    """The program's own spans (``repro.obs`` tracer), switched on for a
    traced run only and returned on the window's clock."""

    def __init__(self, enabled: bool, capacity: int = 1 << 21):
        self.enabled = enabled
        if enabled:
            from repro.obs import TRACER
            TRACER.enable(capacity)
            TRACER.clear()

    def collect(self, w0: float) -> list[dict]:
        """Spans (dicts with ``ts``/``dur`` in seconds from ``w0``)."""
        if not self.enabled:
            return []
        import time

        from repro.obs import TRACER, record
        t = time.perf_counter()
        record("bench_clock", t, t, cat="bench")
        TRACER.disable()
        spans = TRACER.spans()
        mark = next(s for s in reversed(spans) if s["name"] == "bench_clock")
        off = t - mark["ts"] - w0
        out = []
        for s in spans:
            if s["name"] == "bench_clock":
                continue
            s = dict(s)
            s["ts"] += off
            out.append(s)
        return out
