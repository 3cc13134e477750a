"""Scalability benchmark — paper Figure 5.5 analogue, on the CPU.

The paper measures wall-clock vs #cores on EMR. Here scaling is measured
structurally: the sharded MapReduce pipeline runs in a CPU subprocess with
n forced host devices (n in 1,2,4,8; ``JAX_PLATFORMS=cpu``, so a child
never competes with this process for an accelerator); per-shard work and
shuffle volume decrease as 1/n while results stay exact (verified). The
subprocess wall-clock is a CPU number, named as such, and so is the
kernel-level throughput of the hamming sweep (the compute the shards run)
when this process runs on the CPU; its rows carry the platform.

CSV: bench,shards,metric,value
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops


_SHARD_PROBE = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp, time
    from repro.core import encode_batch
    from repro.core.alphabet import AMINO_ACIDS
    from repro.core.simhash import signatures_table
    from repro.core.mapreduce import distributed_flip_join, MapReduceConfig
    n = jax.device_count()
    mesh = jax.make_mesh((n,), ('data',))
    rng = np.random.default_rng(0)
    N = 512
    seqs = [''.join(rng.choice(list(AMINO_ACIDS), 80)) for _ in range(N)]
    ids, lens = encode_batch(seqs, 96)
    sigs = signatures_table(ids, lens, k=3, T=13, f=32)
    qid = jnp.arange(N, dtype=jnp.int32); rid = jnp.arange(N, dtype=jnp.int32)
    # capacity per (src,dst) pair: src holds ~N*34/n records spread over n
    # destinations; 4x headroom for key skew (drops are counted and must be 0)
    cap = max(N * 34 // (n * n) * 4, 1024)
    cfg = MapReduceConfig(n_shards=n, shuffle_capacity=cap,
                          max_pairs_per_shard=65536)
    t0 = time.time()
    pairs, counts, dropped = distributed_flip_join(
        sigs, sigs, qid, rid, f=32, d=1, mesh=mesh, cfg=cfg)
    jax.block_until_ready(pairs)
    t = time.time() - t0
    n_pairs = int((np.asarray(pairs)[..., 0] >= 0).sum())
    # per-shard record volume: (queries + refs*flips) / n
    per_shard = N * (1 + 33) // n
    print(f'RESULT,{n},{t:.3f},{n_pairs},{per_shard},{int(np.asarray(dropped).sum())}')
""")


def run(csv=print):
    csv("bench,shards,metric,value")
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = src
        out = subprocess.run([sys.executable, "-c", _SHARD_PROBE], env=env,
                             capture_output=True, text=True, timeout=900)
        line = [l for l in out.stdout.splitlines()
                if l.startswith("RESULT")]
        if not line:
            csv(f"fig5.5,{n},ERROR,{out.stderr[-200:]!r}")
            continue
        _, shards, t, pairs, per_shard, dropped = line[0].split(",")
        csv(f"fig5.5,{shards},join_wallclock_cpu_s,{t}")
        csv(f"fig5.5,{shards},records_per_shard,{per_shard}")
        csv(f"fig5.5,{shards},pairs,{pairs}")
        csv(f"fig5.5,{shards},dropped,{dropped}")

    # kernel throughput: blocked hamming sweep (the per-shard hot loop)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(0, 2**32, (1024, 2), dtype=np.uint32))
    r = jnp.asarray(rng.integers(0, 2**32, (4096, 2), dtype=np.uint32))
    f = jax.jit(lambda a, b: ops.all_pairs_hamming(a, b, prefer_ref=True))
    f(q, r).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        f(q, r).block_until_ready()
    dt = (time.time() - t0) / 5
    platform = jax.devices()[0].platform
    csv(f"kernel,1,hamming_pairs_per_s_{platform},{1024*4096/dt:.3e}")
    csv(f"kernel,1,hamming_us_per_call_{platform},{dt*1e6:.1f}")
