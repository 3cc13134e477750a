"""Property-based tests (hypothesis). The dependency is a dev extra
(`pip install -e .[dev]`); without it this module skips at collection while
the example-based suites keep running."""
import itertools

import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.alphabet import AMINO_ACIDS, BLOSUM62, encode_batch
from repro.core import simhash
from repro.core.hamming import hamming_distance
from repro.kernels import ref


# ------------------------------------------------------------ python oracle
def naive_signature(seq: str, k: int, T: int, f: int) -> int:
    """Literal Algorithm 2: per-shingle neighbour enumeration, Java hashCode,
    weighted ±1 accumulation, sign bits. (Set semantics of the pseudocode's
    `neighwords` union is a known pseudocode artifact — Figure 3.1 semantics,
    one contribution per (shingle, neighbour word) occurrence, is used, which
    is what the matmul/table paths implement.)"""
    V = [0] * f
    for s in range(len(seq) - k + 1):
        sh = seq[s : s + k]
        for word in itertools.product(AMINO_ACIDS, repeat=k):
            score = sum(
                BLOSUM62[AMINO_ACIDS.index(sh[i]), AMINO_ACIDS.index(word[i])]
                for i in range(k)
            )
            if score >= T:
                h = 0
                for c in word:
                    h = (h * 31 + ord(c)) & 0xFFFFFFFF
                for j in range(f):
                    V[j] += score if (h >> j) & 1 else -score
    bits = [1 if v >= 0 else 0 for v in V]
    out = 0
    for j, b in enumerate(bits):
        out |= b << j
    return out


SEQ = st.text(alphabet=AMINO_ACIDS, min_size=4, max_size=24)


@settings(max_examples=10, deadline=None)
@given(seq=SEQ, T=st.integers(min_value=5, max_value=14))
def test_signature_matches_naive_oracle(seq, T):
    k, f = 2, 32  # k=2 keeps the 400-word oracle loop tractable
    ids, lens = encode_batch([seq])
    got_m = int(np.asarray(simhash.signatures_matmul(ids, lens, k=k, T=T, f=f))[0, 0])
    got_t = int(np.asarray(simhash.signatures_table(ids, lens, k=k, T=T, f=f))[0, 0])
    want = naive_signature(seq, k, T, f)
    assert got_m == want
    assert got_t == want


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
def test_hamming_distance_matches_popcount(a, b):
    d = int(hamming_distance(jnp.uint32([a]), jnp.uint32([b])))
    assert d == bin(a ^ b).count("1")


@settings(max_examples=25, deadline=None)
@given(
    Lq=st.integers(1, 24), Lr=st.integers(1, 24),
    gap=st.integers(-12, -1), seed=st.integers(0, 2**16),
)
def test_gotoh_open_eq_extend_is_linear_sw_cell_exact(Lq, Lr, gap, seed):
    """Gotoh with open == extend degenerates to the linear-gap recurrence
    CELL-exactly: the oracle's full H matrix equals the linear SW DP
    matrix, not just the best score (the property the wavefront's E/F-lane
    zero-init correctness proof leans on)."""
    from repro.align.smith_waterman import _sw_dp
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 20, Lq, dtype=np.int8)
    r = rng.integers(0, 20, Lr, dtype=np.int8)
    best_a, H_a = ref.sw_affine_ref(q, r, gap_open=gap, gap_extend=gap)
    best_l, H_l = _sw_dp(jnp.asarray(q), jnp.asarray(r), return_matrix=True)
    # patched linear DP with the same gap penalty for the H comparison
    H = np.zeros((Lq + 1, Lr + 1), np.int64)
    sub = np.asarray(BLOSUM62)
    for i in range(1, Lq + 1):
        for j in range(1, Lr + 1):
            H[i, j] = max(0, H[i - 1, j - 1] + sub[q[i - 1], r[j - 1]],
                          H[i - 1, j] + gap, H[i, j - 1] + gap)
    np.testing.assert_array_equal(H_a, H)
    assert best_a == H.max()
    if gap == -4:               # the module default: jnp path agrees too
        np.testing.assert_array_equal(np.asarray(H_l), H)
        assert int(best_l) == int(best_a)
