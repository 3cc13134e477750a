"""Small shared utilities."""
from __future__ import annotations

import os
from pathlib import Path


def next_pow2(x: int) -> int:
    """Smallest power of two >= x; 0 stays 0 (callers wanting a nonzero
    floor clamp first — buffer/capacity quantization shared by the
    self-join emission caps and the serving delta slabs)."""
    return 1 << (int(x) - 1).bit_length() if x > 0 else 0


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    the cache and nothing is set here; otherwise the cache lives at the
    fixed ``.jax_cache/`` of the checkout — a stable path, so a later run
    finds what an earlier one compiled. Called by the launchers and
    ``chip_smoke.py``; tests leave the cache off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    # the checkout root: this file is src/repro/util.py
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
