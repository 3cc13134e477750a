"""Shared transformer layers: norms, RoPE, chunked GQA attention, MLP, MoE.

Attention is blockwise ("flash"-style online softmax over KV chunks, scanned
over query chunks) so prefill_32k never materializes an (S, S) score matrix.
The baseline computes all (q-chunk, kv-chunk) tiles with masking — exact but
~2x the causal-optimal attention FLOPs; EXPERIMENTS.md §Perf tracks the
triangular-skip optimization against this honestly-reported baseline.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .sharding import constrain

NEG_INF = -1e30


# ------------------------------------------------------------ norms
def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * scale


def norm(x, scale, kind: str):
    return rmsnorm(x, scale) if kind == "rmsnorm" else layernorm(x, scale)


# ------------------------------------------------------------ RoPE
def rope(x, positions, theta: float):
    """x: (B, S, H, Dh); positions: (S,) int32. Standard rotary embedding.
    (qwen2-vl's M-RoPE degenerates to this for the text/stub-frontend path —
    the three M-RoPE channels share identical position ids; DESIGN.md §4.)
    Negative positions (empty cache slots) are clamped — those slots are
    masked out of attention anyway."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.maximum(positions, 0).astype(jnp.float32)
    ang = pos[:, None] * freqs                                # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention
def _attend_chunk(q, k, v, mask, scale):
    """q (B,qc,Kh,G,Dh) k/v (B,kc,Kh,Dh) mask (B,qc,kc) -> (acc, m, l)."""
    s = jnp.einsum("bqkgd,bckd->bqkgc", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,qc,Kh,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(v.dtype), v)
    return acc, m, l


def flash_attention(q, k, v, *, q_pos, k_pos, causal: bool,
                    window: int | None, chunk: int,
                    causal_skip: bool = False):
    """Blockwise online-softmax attention with explicit position vectors.

    q: (B, Sq, H, Dh); k, v: (B, Skv, Kh, Dh). GQA via grouped einsum
    (no materialized KV repetition). q_pos (Sq,), k_pos (Skv,) int32 are
    absolute positions; k slots with k_pos < 0 are invalid (empty cache
    slots in ring buffers). Returns (B, Sq, H, Dh).

    causal_skip: triangular scheduling — each query block scans only its
    static KV prefix (blocks j <= i), halving causal-attention FLOPs vs the
    masked-full baseline. Requires aligned q/kv (self-attention, no cache)
    and no window. EXPERIMENTS.md §Perf hillclimb B measures the delta.
    """
    B, Sq, H, Dh = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    scale = 1.0 / math.sqrt(Dh)
    qc = min(chunk, Sq)
    kc = min(chunk, Skv)
    nq, nk = -(-Sq // qc), -(-Skv // kc)
    qp = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, qc, Kh, G, Dh)
    kp = kp.reshape(B, nk, kc, Kh, Dh)
    vp = vp.reshape(B, nk, kc, Kh, Dh)
    qpos = jnp.pad(q_pos, (0, nq * qc - Sq),
                   constant_values=-(10**9)).reshape(nq, qc)
    kpos = jnp.pad(k_pos, (0, nk * kc - Skv),
                   constant_values=-1).reshape(nk, kc)

    def q_block(args):
        qb, qpo = args                                        # (B,qc,Kh,G,Dh)

        def kv_step(carry, blk):
            acc, m, l = carry
            kb, vb, kpo = blk
            mask = (kpo >= 0)[None, None, :]
            if causal:
                mask = mask & (qpo[None, :, None] >= kpo[None, None, :])
            if window is not None:
                mask = mask & ((qpo[None, :, None] - kpo[None, None, :])
                               < window)
            mask = jnp.broadcast_to(mask, (B, qc, kc))
            a, m2, l2 = _attend_chunk(qb, kb, vb, mask, scale)
            m_new = jnp.maximum(m, m2)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(m2 - m_new)
            acc = acc * c1[..., None].astype(acc.dtype) + \
                a * c2[..., None].astype(a.dtype)
            l = l * c1 + l2 * c2
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, qc, Kh, G, Dh), qb.dtype)
        m0 = jnp.full((B, qc, Kh, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, Kh, G), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(kp, 1, 0), jnp.moveaxis(vp, 1, 0), kpos))
        return acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)

    if causal_skip and causal and window is None and nq > 1:
        # Triangular schedule: query block i scans only kv blocks 0..i
        # (STATIC prefix per block — python loop, nq separate scans). Total
        # work = S^2/2 + O(S*chunk) instead of the masked-full S^2.
        outs = []
        kp_t = jnp.moveaxis(kp, 1, 0)       # (nk, B, kc, Kh, Dh)
        vp_t = jnp.moveaxis(vp, 1, 0)
        for i in range(nq):
            qb, qpo = qp[:, i], qpos[i]

            def kv_step(carry, blk):
                acc, m, l = carry
                kb, vb, kpo = blk
                mask = (kpo >= 0)[None, None, :] & \
                    (qpo[None, :, None] >= kpo[None, None, :])
                mask = jnp.broadcast_to(mask, (B, qc, kc))
                a, m2, l2 = _attend_chunk(qb, kb, vb, mask, scale)
                m_new = jnp.maximum(m, m2)
                c1, c2 = jnp.exp(m - m_new), jnp.exp(m2 - m_new)
                acc = acc * c1[..., None].astype(acc.dtype) + \
                    a * c2[..., None].astype(a.dtype)
                return (acc, m_new, l * c1 + l2 * c2), None

            acc0 = jnp.zeros((B, qc, Kh, G, Dh), q.dtype)
            m0 = jnp.full((B, qc, Kh, G), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, qc, Kh, G), jnp.float32)
            (acc, m, l), _ = jax.lax.scan(
                kv_step, (acc0, m0, l0),
                (kp_t[: i + 1], vp_t[: i + 1], kpos[: i + 1]))
            outs.append(acc / jnp.maximum(l, 1e-30)[..., None]
                        .astype(acc.dtype))
        out = jnp.stack(outs, axis=1)
    elif nq == 1:
        out = q_block((qp[:, 0], qpos[0]))[:, None]
    else:
        out = jax.lax.map(q_block, (jnp.moveaxis(qp, 1, 0), qpos))
        out = jnp.moveaxis(out, 0, 1)
    out = out.reshape(B, nq * qc, H, Dh)
    return out[:, :Sq]


def _flash_unnormalized(q, k, v, mask, scale, chunk: int):
    """Single-q-block flash returning raw (acc, m, l) — the combinable form
    used by sequence-parallel decode (partial softmax per KV shard, merged
    with pmax/psum across the "model" axis)."""
    B, Sq, Kh, G, Dh = q.shape
    Skv = k.shape[1]
    kc = min(chunk, Skv)
    nk = -(-Skv // kc)
    kp = jnp.pad(k, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))
    mp = jnp.pad(mask, ((0, 0), (0, 0), (0, nk * kc - Skv)))
    kp = jnp.moveaxis(kp.reshape(B, nk, kc, Kh, Dh), 1, 0)
    vp = jnp.moveaxis(vp.reshape(B, nk, kc, Kh, Dh), 1, 0)
    mp = jnp.moveaxis(mp.reshape(B, Sq, nk, kc), 2, 0)

    def kv_step(carry, blk):
        acc, m, l = carry
        kb, vb, mb = blk
        a, m2, l2 = _attend_chunk(q, kb, vb, mb, scale)
        m_new = jnp.maximum(m, m2)
        c1, c2 = jnp.exp(m - m_new), jnp.exp(m2 - m_new)
        acc = acc * c1[..., None].astype(acc.dtype) + \
            a * c2[..., None].astype(a.dtype)
        return (acc, m_new, l * c1 + l2 * c2), None

    acc0 = jnp.zeros((B, Sq, Kh, G, Dh), q.dtype)
    m0 = jnp.full((B, Sq, Kh, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Kh, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (kp, vp, mp))
    return acc, m, l


def seq_sharded_decode_attention(q, cache, k_new, v_new, positions, cfg,
                                 mesh, *, causal=True):
    """Single-token decode against a KV cache whose SEQUENCE axis is sharded
    over the "model" mesh axis (sequence-parallel serving, DESIGN.md §5).

    Every decode_32k cell needs this: the global-attention KV cache is
    12-43 GB per device batch otherwise. Each model shard holds S/|model|
    cache slots, computes a partial flash (acc, m, l) over its slice, and the
    partials merge with pmax/psum — the online-softmax combine is associative
    so the merge is exact.

    q: (B, 1, H, Dh); cache k/v: (B, Smax, Kh, Dh) sharded (dp, model, ..);
    positions: (1,) absolute. Returns (out (B,1,H,Dh), new_cache).
    """
    from jax.sharding import PartitionSpec as P

    B, S, H, Dh = q.shape
    Kh = k_new.shape[2]
    G = H // Kh
    scale = 1.0 / math.sqrt(Dh)
    Smax = cache["k"].shape[1]
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape[a]
    bspec = dp if B % dp_total == 0 else None

    def local_fn(qL, kC, vC, pC, kN, vN, pos):
        # NB: shapes here are PER-SHARD (batch may be dp-sharded, cache seq
        # is model-sharded) — never use the closed-over global B.
        Bl = qL.shape[0]
        idx = jax.lax.axis_index("model")
        Sloc = kC.shape[1]
        slot_g = jnp.mod(pos[0], Smax)
        slot_l = slot_g - idx * Sloc
        inside = (slot_l >= 0) & (slot_l < Sloc)
        sl = jnp.clip(slot_l, 0, Sloc - 1)
        upd = lambda C, N: jnp.where(
            inside, jax.lax.dynamic_update_slice_in_dim(C, N, sl, axis=1), C)
        kC = upd(kC, kN)
        vC = upd(vC, vN)
        pC = jnp.where(inside, jax.lax.dynamic_update_slice_in_dim(
            pC, pos, sl, axis=0), pC)
        kR = rope(kC, pC, cfg.rope_theta)
        qR = qL.reshape(Bl, S, Kh, G, Dh)
        mask = (pC >= 0)[None, None, :]
        if causal:
            mask = mask & (pos[0] >= pC)[None, None, :]
        mask = jnp.broadcast_to(mask, (Bl, S, Sloc))
        acc, m, l = _flash_unnormalized(qR, kR, vC, mask, scale,
                                        cfg.attn_chunk)
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(
            (acc * corr[..., None].astype(acc.dtype)).astype(jnp.float32),
            "model")
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(qL.dtype)
        return out.reshape(Bl, S, H, Dh), kC, vC, pC

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec), P(bspec, "model"), P(bspec, "model"),
                  P("model"), P(bspec), P(bspec), P()),
        out_specs=(P(bspec), P(bspec, "model"), P(bspec, "model"),
                   P("model")), check_vma=False)
    out, ck, cv, cpos = fn(q, cache["k"], cache["v"], cache["pos"],
                           k_new, v_new, positions)
    return out, {"k": ck, "v": cv, "pos": cpos}


def attention_block(x, p, cfg, rules, *, positions, causal: bool,
                    window: int | None, cache=None):
    """Pre-norm GQA attention with optional KV cache (decode).

    p: dict(wq (d, H*hd), wk/wv (d, Kh*hd), wo_attn (H*hd, d), norm (d,)).
    cache: None | dict(k (B, Smax, Kh, hd) UNROPED, v likewise,
    pos (Smax,) absolute positions, -1 = empty, ptr () next write slot).
    Windowed layers use a ring buffer (Smax == window); global layers a
    linear buffer. K is roped at use time from stored positions, so ring
    overwrites stay correct. Returns (out, new_cache).
    """
    B, S, d = x.shape
    H, Kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = norm(x, p["norm"], cfg.norm_type)
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (h @ p["wk"]).reshape(B, S, Kh, hd)
    v = (h @ p["wv"]).reshape(B, S, Kh, hd)
    q = constrain(q, rules, "batch", None, "heads", None)
    k = constrain(k, rules, "batch", None, "kv", None)
    q = rope(q, positions, cfg.rope_theta)

    if cache is None:
        k = rope(k, positions, cfg.rope_theta)
        out = flash_attention(q, k, v, q_pos=positions, k_pos=positions,
                              causal=causal, window=window,
                              chunk=cfg.attn_chunk,
                              causal_skip=cfg.causal_skip)
        new_cache = None
    else:
        Smax = cache["k"].shape[1]
        mesh = rules.get("_mesh")
        seq_shardable = (S == 1 and window is None and mesh is not None
                         and rules.get("kv_seq") == "model"
                         and Smax % mesh.shape["model"] == 0)
        if seq_shardable:
            # Sequence-parallel decode: cache seq axis sharded on "model",
            # partial flash per shard merged with pmax/psum.
            out, new_cache = seq_sharded_decode_attention(
                q, cache, k, v, positions, cfg, mesh, causal=causal)
        elif S == 1:
            # Single-token decode: write-then-attend is exact (the slot
            # written IS the current position; a ring overwrite only evicts
            # pos - Smax, which the window predicate masks anyway) and
            # avoids concatenating a copy of the whole cache every step.
            slots = jnp.mod(positions, Smax)
            ck = cache["k"].at[:, slots].set(k)
            cv = cache["v"].at[:, slots].set(v)
            cpos = cache["pos"].at[slots].set(positions)
            k_roped = rope(ck, cpos, cfg.rope_theta)
            out = flash_attention(q, k_roped, cv, q_pos=positions,
                                  k_pos=cpos, causal=causal, window=window,
                                  chunk=cfg.attn_chunk)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
        else:
            # Chunked prefill: attend BEFORE writing — ring-buffer writes of
            # a multi-token chunk would clobber keys that early queries in
            # the chunk still need. Attention runs over concat(cache, fresh);
            # stale ring entries are masked by the window predicate, empty
            # slots (pos == -1) by the validity predicate.
            ck_in, cv_in, cpos_in = cache["k"], cache["v"], cache["pos"]
            if mesh is not None and rules.get("kv_seq"):
                # XLA SPMD (jax 0.4.37) mis-partitions concatenate along a
                # "model"-sharded axis when the other operand is replicated:
                # the output is the elementwise SUM of the shards, not their
                # concatenation (cache values come out doubled, positions
                # 0..7 become 0,2,..,14 — every slot looks invalid or
                # mis-placed and attention reads garbage). Gathering the
                # cache's seq axis before the concat sidesteps the bug;
                # prefill runs once per sequence, so the all-gather is paid
                # off the decode hot path (which takes the seq-sharded
                # shard_map route above, no concat involved).
                ck_in = constrain(ck_in, rules, "batch", None, "kv", None)
                cv_in = constrain(cv_in, rules, "batch", None, "kv", None)
                cpos_in = constrain(cpos_in, rules, None)
            k_all = jnp.concatenate([ck_in, k], axis=1)
            v_all = jnp.concatenate([cv_in, v], axis=1)
            pos_all = jnp.concatenate([cpos_in, positions])
            k_roped = rope(k_all, pos_all, cfg.rope_theta)
            out = flash_attention(q, k_roped, v_all, q_pos=positions,
                                  k_pos=pos_all, causal=causal,
                                  window=window, chunk=cfg.attn_chunk)
            slots = jnp.mod(positions, Smax)
            ck = cache["k"].at[:, slots].set(k)
            cv = cache["v"].at[:, slots].set(v)
            cpos = cache["pos"].at[slots].set(positions)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
    out = out.reshape(B, S, H * hd) @ p["wo_attn"]
    out = constrain(out, rules, "batch", None, None)
    return out, new_cache


# ------------------------------------------------------------ MLP
def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def mlp_block(x, p, cfg, rules):
    """Pre-norm MLP: gated (SwiGLU-style) or plain, activation per config."""
    h = norm(x, p["norm"], cfg.norm_type)
    u = h @ p["wi"]
    u = constrain(u, rules, "batch", None, "mlp")
    if cfg.mlp_gated:
        g = _act(h @ p["wg"], cfg.mlp_act)
        u = u * g
    else:
        u = _act(u, cfg.mlp_act)
    out = u @ p["wo"]
    return constrain(out, rules, "batch", None, None)


# ------------------------------------------------------------ MoE
def moe_block(x, p, cfg, rules):
    """Dropped-token top-k MoE with SORT-BASED dispatch.

    The classic one-hot dispatch tensor is O(T·E·C) — at train_4k's 1M global
    tokens that is ~1e16 elements. Here dispatch is a gather/scatter over a
    fixed (E·C + 1, d) expert buffer (the +1 row swallows capacity-dropped
    writes), memory O(T·k·cf·d):

      1. route: top-k gates per token (router fp32);
      2. rank each (token, k) within its expert's queue via a stable sort
         over expert ids (the Hadoop-shuffle idiom again — sort-by-key is
         this framework's join primitive, cf. core/mapreduce.py);
      3. scatter kept tokens into slot = e·C + rank;
      4. expert FFN on (E, C, d), E sharded on "model" (EP);
      5. gather + weighted scatter-add back to (T, d).

    Returns (out, aux_loss).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token

    # ROUTING GROUPS: routing/capacity are enforced per batch row (or per
    # the whole batch when S == 1, i.e. decode). Grouping keeps every
    # intermediate carrying the batch axis, so the dp sharding survives the
    # sort/scatter (a single global routing pool would materialize replicated
    # multi-GB gather/scatter buffers — measured 122 GB/device on olmoe
    # train_4k before this change).
    if S == 1:
        groups, Tg = 1, B
    else:
        groups, Tg = B, S
    C = max(int(math.ceil(Tg / E * K * cfg.capacity_factor)), 4)

    h = norm(x, p["norm"], cfg.norm_type).reshape(groups, Tg, d)

    def route_group(hg):
        """hg: (Tg, d) -> (out (Tg, d), me (E,), ce (E,))."""
        logits = hg.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                # (Tg, E)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        e_flat = gate_idx.reshape(Tg * K)
        t_flat = jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), K)
        w_flat = gate_vals.reshape(Tg * K)
        # rank within expert queue (stable sort by expert id)
        order = jnp.argsort(e_flat, stable=True)
        e_s = e_flat[order]
        seg = jnp.concatenate([jnp.ones(1, bool), e_s[1:] != e_s[:-1]])
        idx = jnp.arange(Tg * K, dtype=jnp.int32)
        rank_s = idx - jax.lax.cummax(jnp.where(seg, idx, 0), axis=0)
        rank = jnp.zeros_like(rank_s).at[order].set(rank_s)
        keep = rank < C
        slot = jnp.where(keep, e_flat * C + rank, E * C)       # drop row
        xb = jnp.zeros((E * C + 1, d), hg.dtype).at[slot].set(hg[t_flat])
        xe = xb[: E * C].reshape(E, C, d)
        me = probs.mean(0)
        ce = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0) / (Tg * K) * E
        return xe, (slot, t_flat, w_flat), me, ce

    xe, routing, me, ce = jax.vmap(route_group)(h)             # (G,E,C,d)
    xe = constrain(xe, rules, "batch", "experts", None, None)
    u = jnp.einsum("gecd,edf->gecf", xe, p["ewi"])
    if cfg.mlp_gated:
        g = _act(jnp.einsum("gecd,edf->gecf", xe, p["ewg"]), cfg.mlp_act)
        u = u * g
    else:
        u = _act(u, cfg.mlp_act)
    ye = jnp.einsum("gecf,efd->gecd", u, p["ewo"])             # (G,E,C,d)
    ye = constrain(ye, rules, "batch", "experts", None, None)

    def combine_group(ye_g, routing_g):
        slot, t_flat, w_flat = routing_g
        yb = jnp.concatenate([ye_g.reshape(E * C, d),
                              jnp.zeros((1, d), ye_g.dtype)])  # drop row = 0
        y_rec = yb[slot] * w_flat[:, None].astype(ye_g.dtype)
        return jnp.zeros((Tg, d), ye_g.dtype).at[t_flat].add(y_rec)

    out = jax.vmap(combine_group)(ye, routing).reshape(B, S, d)
    out = constrain(out, rules, "batch", None, None)
    aux = (me.mean(0) * ce.mean(0)).sum()
    return out, aux
