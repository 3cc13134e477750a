"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles.

Property-based (hypothesis) variants live in test_properties.py behind
``pytest.importorskip`` so this module always collects.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, ref


# ------------------------------------------------------------ hamming dist
@pytest.mark.parametrize("Q,R,nw,bq,br", [
    (8, 8, 1, 8, 8),        # exact block fit, f=32
    (37, 61, 2, 16, 32),    # ragged -> padding, f=64
    (256, 128, 4, 128, 128),  # f=128, production-ish tiles
    (5, 300, 1, 8, 256),    # tiny Q, wide R
])
def test_hamming_dist_sweep(Q, R, nw, bq, br):
    rng = np.random.default_rng(Q * 1000 + R)
    q = jnp.asarray(rng.integers(0, 2**32, (Q, nw), dtype=np.uint32))
    r = jnp.asarray(rng.integers(0, 2**32, (R, nw), dtype=np.uint32))
    got = ops.all_pairs_hamming(q, r, bq=bq, br=br)
    want = ref.hamming_dist_ref(q, r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hamming_identity_diagonal():
    rng = np.random.default_rng(1)
    s = jnp.asarray(rng.integers(0, 2**32, (16, 2), dtype=np.uint32))
    d = np.asarray(ops.all_pairs_hamming(s, s, bq=8, br=8))
    assert (np.diag(d) == 0).all()
    assert (d == d.T).all()


# ------------------------------------------------------------ sw / ungapped
def test_interpret_autodetect_off_tpu():
    from repro.kernels.sw import on_tpu, resolve_interpret
    assert resolve_interpret(None) == (not on_tpu())
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.mark.parametrize("B,Lq,Lr,x", [
    (4, 24, 24, 20),       # square block, finite X
    (5, 17, 33, 20),       # ragged -> bb padding
    (8, 16, 16, 2**30),    # x -> inf (plain best ungapped segment)
    (6, 90, 70, 20),       # 159 diagonals: carries cross a block boundary
    (7, 40, 30, None),     # no X-drop test at all (the prefilter's x=None)
])
def test_ungapped_kernel_matches_jnp(B, Lq, Lr, x):
    from repro.align.smith_waterman import ungapped_xdrop_scores
    from repro.core.alphabet import PAD

    rng = np.random.default_rng(B * 100 + Lq)
    qs = rng.integers(0, 20, (B, Lq)).astype(np.int8)
    rs = rng.integers(0, 20, (B, Lr)).astype(np.int8)
    for n in range(B):          # ragged PAD tails
        qs[n, rng.integers(Lq // 2, Lq):] = PAD
        rs[n, rng.integers(Lr // 2, Lr):] = PAD
    got = np.asarray(ops.ungapped_wave_scores(qs, rs, x=x, bb=4))
    want = np.asarray(ungapped_xdrop_scores(
        qs, rs, x=None if x is None or x >= 2**30 else x))
    np.testing.assert_array_equal(got, want)


def test_ungapped_jnp_matches_host_oracle():
    from repro.align.smith_waterman import ungapped_xdrop_scores
    from repro.core.alphabet import PAD

    rng = np.random.default_rng(9)
    for x in (20, None):
        for _ in range(4):
            lq, lr = rng.integers(4, 48, 2)
            q = rng.integers(0, 20, lq).astype(np.int8)
            r = rng.integers(0, 20, lr).astype(np.int8)
            qm = np.full((1, 64), PAD, np.int8)
            rm = np.full((1, 48), PAD, np.int8)
            qm[0, :lq] = q
            rm[0, :lr] = r
            got = int(np.asarray(ungapped_xdrop_scores(qm, rm, x=x))[0])
            assert got == ref.ungapped_xdrop_ref(q, r, 10**9 if x is None
                                                 else x)
