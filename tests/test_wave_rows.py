"""The wave kernels make each anti-diagonal's substitution row in VMEM
(interpret mode on the CPU): every mode against its jnp reference over
shapes whose diagonals span several kernel blocks, and the in-kernel rows
against `_skewed`'s XLA block swept by the same recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.align import gotoh
from repro.align.smith_waterman import GAP, sw_wave_pid, ungapped_xdrop_scores
from repro.core.alphabet import ALPHABET_SIZE, PAD
from repro.kernels import ops, sw

MODES = ["linear", "affine", "ungapped", "ungapped_x20", "pid"]

# (B, Lq, Lr): B is no multiple of the kernel's 8-pair block, and every
# length a multiple of 64 but (but for 64) not of 128
SHAPES = {
    "tall": (10, 192, 64),      # Lq >> Lr: 255 diagonals, 2 blocks
    "wide": (9, 64, 320),       # Lr >> Lq: 383 diagonals, 3 blocks
    "square": (12, 320, 192),   # 511 diagonals, 4 blocks
}


def _block(B, Lq, Lr, seed):
    """(B, Lq) x (B, Lr) int8 pairs, PAD-padded: homologous pairs (a
    query and its 15%-substituted copy, shifted) with ragged tails, a
    random pair, an all-PAD query and an all-PAD reference."""
    rng = np.random.default_rng(seed)
    qs = np.full((B, Lq), PAD, np.int8)
    rs = np.full((B, Lr), PAD, np.int8)
    for b in range(B):
        s = rng.integers(0, ALPHABET_SIZE, Lq + Lr).astype(np.int8)
        r = np.where(rng.random(len(s)) < 0.15,
                     rng.integers(0, ALPHABET_SIZE, len(s)), s)
        lq = int(rng.integers(1, Lq + 1))
        lr = int(rng.integers(1, Lr + 1))
        off = int(rng.integers(0, Lr))
        qs[b, :lq] = s[off:off + lq]
        rs[b, :lr] = r[:lr]
    qs[1, :] = rng.integers(0, ALPHABET_SIZE, Lq)      # unrelated pair
    qs[2, :] = PAD
    rs[3, :] = PAD
    return qs, rs


def _kernel(mode, qs, rs):
    if mode == "pid":
        return np.asarray(ops.wavefront_pid(qs, rs, interpret=True))
    if mode.startswith("ungapped"):
        x = 20 if mode.endswith("x20") else None
        return np.asarray(ops.ungapped_wave_scores(qs, rs, x=x,
                                                   interpret=True))
    return np.asarray(ops.wavefront_scores(qs, rs, gap_mode=mode,
                                           interpret=True))


def _reference(mode, qs, rs):
    if mode == "pid":
        pid, length, score = sw_wave_pid(qs, rs)
        return score, pid, length
    if mode.startswith("ungapped"):
        x = 20 if mode.endswith("x20") else None
        return np.asarray(ungapped_xdrop_scores(qs, rs, x=x))
    if mode == "affine":
        return np.asarray(gotoh.sw_wave_affine(qs, rs))
    return np.asarray(gotoh.sw_wave_linear(qs, rs, gap=GAP))


def _asymmetric_table(mode, seed):
    """A random substitution table with table[a, b] != table[b, a], so a
    row made from the transposed lookup cannot pass; PAD's row and column
    SENT8, and in ``pid`` mode the 2 * score + match coding."""
    rng = np.random.default_rng(seed)
    t = rng.integers(-4, 12, (ALPHABET_SIZE + 1,) * 2)
    if mode == "pid":
        t = 2 * t + np.eye(ALPHABET_SIZE + 1, dtype=t.dtype)
    t[PAD, :] = sw.SENT8
    t[:, PAD] = sw.SENT8
    return t.astype(np.int8)


def _rows_call(route, mode, qs, rs, table):
    """One mode's pallas_call over (qs, rs) with rows made in the kernel
    (``route="kernel"``) or by `_skewed` in XLA (``"skewed"``)."""
    mode_kw = {"linear": dict(mode="linear", gap_open=GAP, gap_extend=GAP),
               "affine": dict(mode="affine", gap_open=gotoh.GAP_OPEN,
                              gap_extend=gotoh.GAP_EXTEND),
               "ungapped": dict(mode="ungapped"),
               "ungapped_x20": dict(mode="ungapped", x=20),
               "pid": dict(mode="pid", gap_open=GAP)}[mode]
    q, r = jnp.asarray(qs), jnp.asarray(rs)
    if route == "kernel":
        out = sw._wave_rows(q, r, table, bb=8, interpret=True, **mode_kw)
    else:
        out = sw._wave_call(sw._skewed(q, r, table), bb=8, interpret=True,
                            **mode_kw)
    return [np.asarray(o) for o in jax.tree.leaves(out)]


@pytest.mark.parametrize("case", [*SHAPES, "skewed_rows"])
@pytest.mark.parametrize("mode", MODES)
def test_in_kernel_rows_match_reference(mode, case):
    if case == "skewed_rows":
        # the same recurrence over both row sources, an asymmetric table,
        # B a multiple of 8 as `_wave_call` takes it
        qs, rs = _block(16, 192, 320, seed=sum(map(ord, mode)))
        table = _asymmetric_table(mode, seed=sum(map(ord, mode)))
        got = _rows_call("kernel", mode, qs, rs, table)
        want = _rows_call("skewed", mode, qs, rs, table)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(g, w)
        assert got[0].max() > 0
        return
    qs, rs = _block(*SHAPES[case], seed=sum(map(ord, case + mode)))
    got = _kernel(mode, qs, rs)
    want = _reference(mode, qs, rs)
    if mode == "pid":
        score, pid, length = want
        np.testing.assert_array_equal(got[:, 0], score)
        np.testing.assert_array_equal(got[:, 2], length)
        np.testing.assert_array_equal(
            100.0 * got[:, 1] / np.maximum(got[:, 2], 1), pid)
        got = got[:, 0]
    else:
        np.testing.assert_array_equal(got, want)
    assert got[2] == 0 and got[3] == 0          # the all-PAD rows
    assert got.max() > 20                       # the homologs align
