"""Distributed MapReduce engine: shuffle/reduce/salting/ring-sweep.

Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the main test process
keeps the default 1-device view, per the brief).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.mapreduce import reduce_join, salt_hot_keys


def test_reduce_join_cross_product():
    # bucket 7: queries {10, 11}, refs {20, 21, 22} -> 6 pairs
    # bucket 9: query {12}, ref {23}               -> 1 pair
    # bucket 5: refs only                          -> 0 pairs
    keys = jnp.uint32([7, 7, 7, 7, 7, 9, 9, 5, 0xFFFFFFFF])
    ids = jnp.int32([10, 20, 11, 21, 22, 12, 23, 24, -1])
    isq = jnp.int32([1, 0, 1, 0, 0, 1, 0, 0, 0])
    pairs, total = reduce_join(keys, jnp.stack([ids, isq], -1), max_pairs=32)
    got = {(int(a), int(b)) for a, b in np.asarray(pairs) if a >= 0}
    want = {(10, 20), (10, 21), (10, 22), (11, 20), (11, 21), (11, 22),
            (12, 23)}
    assert got == want and int(total) == 7


def test_reduce_join_overflow_reports_true_total():
    keys = jnp.uint32([3] * 8)
    ids = jnp.int32([0, 1, 2, 3, 100, 101, 102, 103])
    isq = jnp.int32([1, 1, 1, 1, 0, 0, 0, 0])  # 4 queries x 4 refs = 16
    pairs, total = reduce_join(keys, jnp.stack([ids, isq], -1), max_pairs=5)
    assert int(total) == 16  # true count, even though only 5 emitted
    assert (np.asarray(pairs)[:, 0] >= 0).sum() == 5


def test_salting_rekeys_only_hot_refs():
    keys = jnp.uint32([42] * 10 + [7, 8])
    isq = jnp.asarray([True, True] + [False] * 10)
    new, hot = salt_hot_keys(keys, hot_threshold=4, n_salt=4, is_query=isq,
                             replicate_queries=False)
    new = np.asarray(new)
    assert bool(hot[0]) and not bool(hot[-1])
    assert new[0] == 42 and new[1] == 42          # queries keep their key
    assert (new[2:10] != 42).all()                # hot refs re-keyed
    assert new[10] == 7 and new[11] == 8          # cold keys untouched
    assert len(set(new[2:10].tolist())) <= 4      # at most n_salt sub-buckets


_DISTRIBUTED_CHECK = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import encode_batch
    from repro.core.alphabet import AMINO_ACIDS
    from repro.core.simhash import signatures_table
    from repro.core.mapreduce import (distributed_flip_join, MapReduceConfig,
                                      ring_sweep)
    from repro.core.join import flip_join, pairs_to_set

    assert jax.device_count() == 4
    mesh = jax.make_mesh((4,), ('data',))
    rng = np.random.default_rng(0)
    refs = [''.join(rng.choice(list(AMINO_ACIDS), 60)) for _ in range(32)]
    qrys = [r[:55] for r in refs[:8]] + \\
           [''.join(rng.choice(list(AMINO_ACIDS), 60)) for _ in range(24)]
    rids_, rlen = encode_batch(refs, 64)
    qids_, qlen = encode_batch(qrys, 64)
    rs = signatures_table(rids_, rlen, k=3, T=13, f=32)
    qs = signatures_table(qids_, qlen, k=3, T=13, f=32)
    pt, _ = flip_join(qs, rs, f=32, d=1, max_pairs=4096)
    truth = pairs_to_set(pt)
    qid = jnp.arange(32, dtype=jnp.int32); rid = jnp.arange(32, dtype=jnp.int32)
    for salting in (False, True):
        cfg = MapReduceConfig(n_shards=4, shuffle_capacity=2048,
                              max_pairs_per_shard=4096, salting=salting)
        pairs, counts, dropped = distributed_flip_join(
            qs, rs, qid, rid, f=32, d=1, mesh=mesh, cfg=cfg)
        got = pairs_to_set(np.asarray(pairs).reshape(-1, 2))
        assert np.asarray(dropped).sum() == 0
        assert got == truth, (salting, got ^ truth)
    rp, rc = ring_sweep(qs, rs, d=1, mesh=mesh, max_pairs_per_shard=4096)
    assert pairs_to_set(np.asarray(rp).reshape(-1, 2)) == truth
    # Skew stress: 16 identical ref signatures (one hot bucket) + salting.
    rs_hot = jnp.tile(rs[:1], (16, 1))
    qs_hot = jnp.tile(qs[:1], (4, 1))
    pt2, _ = flip_join(qs_hot, rs_hot, f=32, d=0, max_pairs=4096)
    truth2 = pairs_to_set(pt2)
    cfg = MapReduceConfig(n_shards=4, shuffle_capacity=2048,
                          max_pairs_per_shard=4096, salting=True,
                          hot_threshold=2, n_salt=4)
    pairs, _, dropped = distributed_flip_join(
        qs_hot, rs_hot, jnp.arange(4, dtype=jnp.int32),
        jnp.arange(16, dtype=jnp.int32), f=32, d=0, mesh=mesh, cfg=cfg)
    got2 = pairs_to_set(np.asarray(pairs).reshape(-1, 2))
    assert np.asarray(dropped).sum() == 0
    assert got2 == truth2, got2 ^ truth2
    print('DISTRIBUTED_OK')
""")


@pytest.mark.slow
def test_distributed_join_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices: a CPU run
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _DISTRIBUTED_CHECK],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DISTRIBUTED_OK" in out.stdout
