"""Compile the main-path Pallas kernels for a v5e chip, without the chip.

The TPU compiler is installed with jax, so each kernel is lowered and
compiled here for a described (not attached) v5e at the widths the main
path sends it: Swiss-Prot-shaped DP waves padded to ``len_quantum=64``,
the longest wave the ``max_wave_cells`` plan sends, the all-pairs corpus's
emission slabs, and a dense top-k sweep over a Swiss-Prot-sized index.
Interpret-mode tests cannot see what this catches: a dynamic slice Mosaic
cannot lower, a block that breaks the (8, 128) tiling rule, a kernel past
the scoped VMEM limit. The wave programs must also hold no skewed
(diagonal, pair, query) substitution block: the kernels make those rows
in VMEM. Nothing runs, so results are the other tests' job.
"""
from __future__ import annotations

import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.allpairs import WaveConfig
from repro.kernels.hamming import hamming_dist_kernel
from repro.kernels.spgemm import upper_pairs_kernel
from repro.kernels.sw import (DIAG_BLOCK, ungapped_scores_kernel,
                              wave_pid_kernel, wave_scores_kernel)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_op(text: str, name: str) -> bool:
    """True iff the compiled program holds a Pallas kernel whose op XLA
    named after ``name`` (``%name.1 = ... custom-call(...)``, its result
    one array or a tuple), the name the benchmark's profile readers look
    for."""
    return re.search(rf"%{name}(\.\d+)? = (\(.*?\)|\S+) custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", text) \
        is not None


def _skewed_arrays(text: str, B: int, Lq: int, Lr: int) -> list[str]:
    """The arrays of a compiled wave program that hold a skewed
    substitution block: their dims hold B, and the diagonal count (nd, or
    nd padded to a ``DIAG_BLOCK`` multiple) with Lq or flattened with it,
    in any layout, as XLA's gather, transposes and pad of ``(nd, B, Lq)``
    rows make them (``s8[639,320,88]``, ``s8[204480,88]`` at
    Lq = Lr = 320)."""
    nd = Lq + Lr - 1
    found = []
    for m in re.finditer(r"\b[a-z]+\d*\[(\d+(?:,\d+)+)\]", text):
        dims = Counter(int(d) for d in m.group(1).split(","))
        for d in {nd, -(-nd // DIAG_BLOCK) * DIAG_BLOCK}:
            if any(dims >= Counter(want) for want in ([d, B, Lq],
                                                      [d * Lq, B])):
                found.append(m.group(0))
    return found


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _wave_batch(batch: int, L: int, Lr: int | None = None) -> int:
    """The pair batch ``allpairs.tiles`` sends at (L, Lr or L) under the
    default cell budget, padded to the kernel's 8-pair block."""
    b = max(1, min(batch, WaveConfig().max_wave_cells // (L * (Lr or L))))
    return -(-b // 8) * 8


@pytest.mark.parametrize("gap_mode", ["linear", "affine"])
@pytest.mark.parametrize("L", [384, 1024])
def test_wave_scores_kernel_compiles(one_chip, gap_mode, L):
    B = _wave_batch(WaveConfig().wave_batch, L)
    text = _compiled_text(
        lambda q, r: wave_scores_kernel(q, r, gap_mode=gap_mode,
                                        interpret=False),
        one_chip, ((B, L), jnp.int8), ((B, L), jnp.int8))
    assert "tpu_custom_call" in text
    assert _kernel_op(text, "wavefront_dp")
    assert not _skewed_arrays(text, B, L, L)


# PID waves of the NC_000913 corpus (lengths N(316, 80), padded to 64):
# the fullest bucket, a ragged one, and past its longest protein
@pytest.mark.parametrize("Lq,Lr", [(320, 320), (256, 704), (640, 640),
                                   (1024, 1024)])
def test_wave_pid_kernel_compiles(one_chip, Lq, Lr):
    B = _wave_batch(WaveConfig().wave_batch, Lq, Lr)
    text = _compiled_text(
        lambda q, r: wave_pid_kernel(q, r, interpret=False),
        one_chip, ((B, Lq), jnp.int8), ((B, Lr), jnp.int8))
    assert "tpu_custom_call" in text
    assert _kernel_op(text, "wavefront_pid")
    assert not _skewed_arrays(text, B, Lq, Lr)


@pytest.mark.parametrize("L,x", [(128, None), (384, None), (384, 20),
                                 (1024, None)])
def test_ungapped_scores_kernel_compiles(one_chip, L, x):
    B = _wave_batch(WaveConfig().prefilter_batch, L)
    text = _compiled_text(
        lambda q, r: ungapped_scores_kernel(q, r, x=x, interpret=False),
        one_chip, ((B, L), jnp.int8), ((B, L), jnp.int8))
    assert "tpu_custom_call" in text
    assert _kernel_op(text, "ungapped_prefilter")
    assert not _skewed_arrays(text, B, L, L)


# (G bands, U+1 offsets, E entries, cap) of the NC_000913-shaped corpus
# (4,146 sequences, k=3 T=13 f=32): the one-shard d=0 flip slab, and one
# device's slab of the 4-shard d=1 band self-join
@pytest.mark.parametrize("G,U1,E,cap", [(1, 1662, 4146, 8192),
                                        (2, 22, 1331, 131072)])
def test_upper_pairs_kernel_compiles(one_chip, G, U1, E, cap):
    text = _compiled_text(
        lambda o, i: upper_pairs_kernel(o, i, cap=cap, interpret=False),
        one_chip, ((G, U1), jnp.int32), ((G, E), jnp.int32))
    assert "tpu_custom_call" in text


def test_hamming_dist_kernel_compiles(one_chip):
    # one 64-query serving batch (padded to the 256-row block) against a
    # 454,401-reference index (padded to 454,656), f=32
    text = _compiled_text(
        lambda q, r: hamming_dist_kernel(q, r, interpret=False),
        one_chip, ((256, 1), jnp.uint32), ((454656, 1), jnp.uint32))
    assert "tpu_custom_call" in text
