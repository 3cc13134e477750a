"""Gradient compression: int8 quantized all-reduce with error feedback.

Semantics note (why this is NOT wired inside the pjit train step): under
GSPMD, by the time gradients are visible as values they are already globally
reduced — there is no seam to compress. Compressed reduction therefore
belongs to *explicit* data-parallel execution: a `shard_map` step where each
DP shard computes local grads and the cross-shard mean is an explicit
collective we control. That is exactly the deployment where compression
matters (the cross-pod DCI hop, the scarcest bandwidth in the production
mesh); intra-pod reductions stay fp32 under GSPMD.

Provides:
  * quantize_int8 / dequantize_int8 — blockwise symmetric int8 (scale =
    max|g|/127 per 2048-block): 4x traffic cut, one fp32 scale per block.
  * compressed_dp_mean — int8 psum-mean inside shard_map, with the
    quantization residual returned for error feedback (Karimireddy et al.
    2019: feeding the residual into the next step keeps SGD convergence).
  * make_compressed_dp_step — a complete explicit-DP train step (used by the
    elastic/compression example and tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


BLOCK = 2048


def quantize_int8(g, block: int = BLOCK):
    """g (flat fp32) -> (q (nb, block) int8, scales (nb, 1) fp32, true_len)."""
    n = g.shape[0]
    nb = -(-n // block)
    gp = jnp.pad(g, (0, nb * block - n)).reshape(nb, block)
    scale = jnp.max(jnp.abs(gp), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(gp / jnp.maximum(scale, 1e-12)),
                 -127, 127).astype(jnp.int8)
    return q, scale, n


def dequantize_int8(q, scale, n):
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def compressed_dp_mean(g_flat, axis_name: str):
    """int8-compressed mean over `axis_name` (call inside shard_map).

    Returns (mean fp32, residual fp32) — residual = what quantization lost
    locally; callers add it to the next step's gradient (error feedback).
    The wire format is (int8 payload, fp32 scales): the psum itself runs on
    the dequantized payload, modelling the 4x-smaller transfer.
    """
    q, scale, n = quantize_int8(g_flat)
    deq = dequantize_int8(q, scale, n)
    residual = g_flat - deq
    total = jax.lax.psum(deq, axis_name)
    return total / jax.lax.psum(1.0, axis_name), residual


def tree_to_vec(tree):
    flat, treedef = jax.tree.flatten(tree)
    sizes = [x.size for x in flat]
    shapes = [x.shape for x in flat]
    dtypes = [x.dtype for x in flat]
    vec = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in flat])
    return vec, (treedef, sizes, shapes, dtypes)


def vec_to_tree(vec, meta):
    treedef, sizes, shapes, dtypes = meta
    out, off = [], 0
    for sz, shp, dt in zip(sizes, shapes, dtypes):
        out.append(vec[off:off + sz].reshape(shp).astype(dt))
        off += sz
    return treedef.unflatten(out)


def make_compressed_dp_step(loss_fn, mesh, axis_name: str = "data",
                            lr: float = 1e-2, error_feedback: bool = True):
    """Explicit-DP SGD step with int8-compressed gradient mean.

    loss_fn(params, batch) -> scalar; params replicated, batch sharded on
    axis 0 across `axis_name`. State: (params, residual_vec).
    Returns step(state, batch) -> (state, loss_mean).
    """
    def local_step(params, residual, batch):
        # residual arrives (1, nvec) — this shard's slice of the stacked
        # per-shard residual state.
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        gvec, meta = tree_to_vec(grads)
        if error_feedback:
            gvec = gvec + residual[0]
        gmean, new_residual = compressed_dp_mean(gvec, axis_name)
        pvec, pmeta = tree_to_vec(params)
        new_params = vec_to_tree(pvec - lr * gmean, pmeta)
        return (new_params, new_residual[None],
                jax.lax.pmean(loss, axis_name))

    def step(state, batch):
        params, residual = state      # residual: (n_shards, nvec)
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=(P(), P(axis_name), P()), check_vma=False)
        new_params, new_res, loss = fn(params, residual, batch)
        return (new_params, new_res), loss

    def init_residual(params):
        nvec = sum(x.size for x in jax.tree.leaves(params))
        return jnp.zeros((mesh.shape[axis_name], nvec), jnp.float32)

    step.init_residual = init_residual
    return step
