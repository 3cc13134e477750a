"""Training runtime: optimizer math, grad accumulation invariance,
checkpoint/restart (fault tolerance), gradient compression numerics."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import init_params, loss_fn
from repro.train import (AdamWConfig, TrainConfig, adamw_init, adamw_update,
                         init_train_state, make_train_step, warmup_cosine)
from repro.train.compression import (quantize_int8, dequantize_int8,
                                     tree_to_vec, vec_to_tree)
from repro.checkpoint import CheckpointManager
from repro.data.lm_data import LMDataConfig, lm_batches


def _smoke_setup(n_micro=1):
    cfg = get_smoke_config("yi-9b")
    tc = TrainConfig(n_microbatches=n_micro,
                     opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50))
    step = make_train_step(cfg, tc, mesh=None)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    return cfg, step, state, dc


# ------------------------------------------------------------ optimizer
def test_adamw_decreases_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3, 1))}

    cfg = AdamWConfig(lr=5e-2, weight_decay=0.0, warmup_steps=0,
                      total_steps=1000, min_lr_ratio=1.0)
    state = adamw_init(params)
    for _ in range(300):
        g = {"w": (params["w"][:, 0] - target)[:, None]}
        params, state, _ = adamw_update(g, state, params, cfg)
    np.testing.assert_allclose(np.asarray(params["w"])[:, 0], target,
                               atol=1e-2)


def test_warmup_cosine_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(warmup_cosine(cfg, s)) for s in range(101)]
    assert lrs[0] == 0.0 and abs(lrs[10] - 1.0) < 1e-6
    assert abs(lrs[100] - 0.1) < 1e-6
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))  # decaying


def test_master_weights_preserve_bf16_params_dtype():
    cfg = get_smoke_config("yi-9b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = adamw_init(params)
    g = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32), params)
    new_params, state, _ = adamw_update(
        g, state, params, AdamWConfig(warmup_steps=0))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)):
        assert a.dtype == b.dtype
    # masters stay fp32
    assert all(m.dtype == jnp.float32
               for m in jax.tree.leaves(state["master"]))


# ------------------------------------------------------------ grad accum
def test_grad_accum_matches_full_batch():
    """n_microbatches=4 must equal n_microbatches=1 up to fp tolerance."""
    cfg = get_smoke_config("yi-9b").scaled(dtype="float32")
    tc1 = TrainConfig(n_microbatches=1, opt=AdamWConfig(warmup_steps=0))
    tc4 = TrainConfig(n_microbatches=4, opt=AdamWConfig(warmup_steps=0))
    s1 = init_train_state(jax.random.PRNGKey(0), cfg)
    s4 = init_train_state(jax.random.PRNGKey(0), cfg)
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    x, y = lm_batches(dc, 0)
    batch = {"inputs": x, "targets": y}
    step1 = make_train_step(cfg, tc1, None)
    step4 = make_train_step(cfg, tc4, None)
    s1b, m1 = step1(s1, batch)
    s4b, m4 = step4(s4, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1b.params), jax.tree.leaves(s4b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


def test_loss_decreases_over_steps():
    cfg, step, state, dc = _smoke_setup()
    step = jax.jit(step)
    losses = []
    for s in range(12):
        x, y = lm_batches(dc, 0)  # same batch -> must memorize
        state, m = step(state, {"inputs": x, "targets": y})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip_bitwise(tmp_path):
    cfg, step, state, dc = _smoke_setup()
    mgr = CheckpointManager(tmp_path, keep_last=2)
    mgr.save(3, state)
    restored, s = mgr.restore(state)
    assert s == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_last_n(tmp_path):
    cfg, step, state, dc = _smoke_setup()
    mgr = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.ones(3) * s})
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_restart_continuation_is_bitwise(tmp_path):
    """Kill/restart invariant: train 6 steps straight == train 3, checkpoint,
    'crash', restore, train 3 more (deterministic stateless data)."""
    def run(n_start, n_end, state):
        cfg, step, _, dc = _smoke_setup()
        step = jax.jit(step)
        for s in range(n_start, n_end):
            x, y = lm_batches(dc, s)
            state, _ = step(state, {"inputs": x, "targets": y})
        return state

    cfg, step, state0, dc = _smoke_setup()
    straight = run(0, 6, state0)

    mgr = CheckpointManager(tmp_path)
    mid = run(0, 3, state0)
    mgr.save(3, mid)
    del mid                                 # "crash"
    restored, s = mgr.restore(straight)     # template only provides structure
    resumed = run(3, 6, restored)
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_save_survives_partial_tmp(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"x": jnp.arange(4)})
    # simulate a crash mid-write of step 2: stale tmp dir, no manifest
    (tmp_path / "step_00000002.tmp").mkdir()
    assert mgr.latest_step() == 1
    restored, s = mgr.restore({"x": jnp.zeros(4, jnp.int32)})
    assert s == 1


# ------------------------------------------------------------ compression
def test_int8_quantization_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=5000).astype(np.float32))
    q, scale, n = quantize_int8(g)
    back = dequantize_int8(q, scale, n)
    err = np.abs(np.asarray(back - g))
    per_block_bound = np.repeat(np.asarray(scale)[:, 0] * 0.5 + 1e-9, 2048)[:5000]
    assert (err <= per_block_bound).all()


def test_tree_vec_roundtrip():
    tree = {"a": jnp.ones((3, 2), jnp.bfloat16), "b": jnp.arange(5.0)}
    vec, meta = tree_to_vec(tree)
    back = vec_to_tree(vec, meta)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


_COMPRESSED_DP = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.train.compression import make_compressed_dp_step
    assert jax.device_count() == 4
    mesh = jax.make_mesh((4,), ('data',))
    # least squares: loss(w) = mean((x@w - y)^2), data sharded across devices
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    w_true = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    Y = X @ w_true
    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params['w'] - y) ** 2)
    step = make_compressed_dp_step(loss_fn, mesh, 'data', lr=0.1)
    params = {'w': jnp.zeros(8)}
    state = (params, step.init_residual(params))
    for i in range(200):
        state, loss = step(state, (X, Y))
    final = float(loss)
    assert final < 1e-3, final
    print('COMPRESSED_DP_OK', final)
""")


@pytest.mark.slow
def test_compressed_dp_convergence_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices: a CPU run
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _COMPRESSED_DP],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMPRESSED_DP_OK" in out.stdout
