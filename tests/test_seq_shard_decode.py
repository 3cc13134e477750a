"""Sequence-parallel KV-cache decode == unsharded decode (exactness).

The decode_32k cells depend on seq_sharded_decode_attention (cache seq axis
on "model" with a pmax/psum flash combine). This test runs the same decode
on a (2, 2) ("data","model") mesh with the sharded cache and on a plain
1-device path, and demands matching logits.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_CHECK = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import (ModelConfig, init_params, init_cache,
                              decode_step, prefill)
    from repro.models.sharding import make_rules, cache_spec_tree

    assert jax.device_count() == 4
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      attn_chunk=8, ce_chunk=8, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 4, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    MAXLEN = 16  # divisible by model axis (2) -> seq-shard path triggers

    # ---- reference: plain decode, no mesh
    cache0 = init_cache(cfg, B, MAXLEN)
    lg_ref, c_ref = prefill(params, toks[:, :8], cache0, cfg)
    outs_ref = [lg_ref]
    cr = c_ref
    for t in range(8, S):
        lg, cr = decode_step(params, cr, toks[:, t:t+1], jnp.int32(t), cfg)
        outs_ref.append(lg)

    # ---- sharded: cache seq axis on "model"
    rules = make_rules(cfg, mesh)
    assert rules["kv_seq"] == "model"
    with mesh:
        cache = init_cache(cfg, B, MAXLEN)
        cspecs = cache_spec_tree(cache, cfg, rules)
        cache = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            cache, cspecs, is_leaf=lambda x: hasattr(x, "shape"))
        # prefill runs the chunked (concat) path; decode the seq-shard path
        lg, cache = prefill(params, toks[:, :8], cache, cfg, rules)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(outs_ref[0]),
                                   rtol=2e-4, atol=2e-4)
        dstep = jax.jit(partial(decode_step, cfg=cfg, rules=rules))
        for i, t in enumerate(range(8, S)):
            lg, cache = dstep(params, cache, toks[:, t:t+1], jnp.int32(t))
            np.testing.assert_allclose(np.asarray(lg),
                                       np.asarray(outs_ref[i + 1]),
                                       rtol=2e-4, atol=2e-4)
    print("SEQ_SHARD_DECODE_OK")
""")


@pytest.mark.slow
def test_seq_sharded_decode_matches_unsharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices: a CPU run
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _CHECK],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SEQ_SHARD_DECODE_OK" in out.stdout
