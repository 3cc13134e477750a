"""The paper's MapReduce layer, restated on `shard_map` + JAX collectives.

Hadoop concept -> TPU-native construct (DESIGN.md §2):

  map task        -> per-device shard compute inside `shard_map`
  shuffle         -> `jax.lax.all_to_all` routing records to owner shard
                     (owner = key mod n_shards)
  reduce-by-key   -> on-owner `sort` by key + segment-boundary cross-product
  speculative     -> hot-bucket *salting*: keys whose bucket exceeds a cap are
  re-execution       split across shards by a salt so no single reducer
                     receives a skewed bucket (the straggler fix native to
                     this domain)
  HDFS            -> fixed-capacity on-device buffers + checkpoint manifests

Everything is fixed-shape (SPMD): shuffles move exactly `capacity` records
per (src, dst) shard pair; overflow is *counted and reported*, never silent
(DESIGN.md §5 "no silent caps").

Also provides `ring_sweep`: the streaming alternative to the shuffle — the
reference set stays sharded and blocks rotate around the ring via
`lax.ppermute`, overlapping each block's Hamming sweep with the transfer of
the next (comm/compute overlap without a global barrier).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .hamming import hamming_distance


# ----------------------------------------------------------------- shuffle
def shuffle_records(keys, payload, *, axis_name: str, n_shards: int,
                    capacity: int):
    """Route (key, payload) records to owner shard = key % n_shards.

    Per-shard inputs (inside shard_map):
      keys: (n,) uint32 — join keys; key==0xFFFFFFFF marks an empty slot.
      payload: (n, p) int32.
    Returns (keys', payload', dropped) where keys'/payload' hold up to
    `n_shards*capacity` received records and `dropped` counts overflow
    records (per destination) that could not be packed.
    """
    n = keys.shape[0]
    EMPTY = jnp.uint32(0xFFFFFFFF)
    dst = (keys % jnp.uint32(n_shards)).astype(jnp.int32)
    dst = jnp.where(keys == EMPTY, -1, dst)

    # Pack records destined for shard s into row s of a (n_shards, capacity)
    # send buffer. rank_within_dst = stable per-destination arrival order.
    order = jnp.argsort(jnp.where(dst < 0, n_shards, dst), stable=True)
    dst_s = dst[order]
    seg_start = jnp.concatenate([jnp.ones(1, bool), dst_s[1:] != dst_s[:-1]])
    pos = jnp.arange(n) - jax.lax.cummax(
        jnp.where(seg_start, jnp.arange(n), 0), axis=0
    )
    ok = (dst_s >= 0) & (pos < capacity)
    send_k = jnp.full((n_shards, capacity), EMPTY, jnp.uint32)
    send_p = jnp.full((n_shards, capacity) + payload.shape[1:], -1, payload.dtype)
    flat = jnp.where(ok, dst_s * capacity + pos.astype(jnp.int32), 0)
    send_k = send_k.ravel().at[flat].set(
        jnp.where(ok, keys[order], send_k.ravel()[flat])).reshape(n_shards, capacity)
    pf = payload[order]
    send_p = send_p.reshape(n_shards * capacity, -1).at[flat].set(
        jnp.where(ok[:, None], pf.reshape(n, -1),
                  send_p.reshape(n_shards * capacity, -1)[flat])
    ).reshape((n_shards, capacity) + payload.shape[1:])
    dropped = jnp.sum((dst_s >= 0) & ~ok)

    # The shuffle itself: one all_to_all per tensor.
    recv_k = jax.lax.all_to_all(send_k, axis_name, 0, 0, tiled=False)
    recv_p = jax.lax.all_to_all(send_p, axis_name, 0, 0, tiled=False)
    return (recv_k.reshape(n_shards * capacity),
            recv_p.reshape((n_shards * capacity,) + payload.shape[1:]),
            dropped)


# ----------------------------------------------------------------- salting
def salt_hot_keys(keys, *, hot_threshold: int, n_salt: int, is_query,
                  replicate_queries: bool):
    """Split oversized buckets: refs in a hot bucket get key ^= salt<<24 with
    salt = slot % n_salt; queries in hot buckets are replicated across all
    salts (done by the caller via `query_salt_copies`). Here we just detect
    hot keys and re-key references.

    Returns (new_keys, hot_mask). Detection is per-shard (approximate global
    histogram — exact detection would need a count shuffle; per-shard counts
    upper-bound skew well for hash-distributed keys, and correctness never
    depends on detection: salting only *re-buckets*, the exact filter runs
    after the join).
    """
    order = jnp.argsort(keys)
    ks = keys[order]
    seg = jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    seg_id = jnp.cumsum(seg) - 1
    counts = jnp.zeros(keys.shape[0], jnp.int32).at[seg_id].add(1)
    hot_sorted = counts[seg_id] > hot_threshold
    hot = jnp.zeros(keys.shape[0], bool).at[order].set(hot_sorted)
    salt = (jnp.arange(keys.shape[0], dtype=jnp.uint32) % jnp.uint32(n_salt)) + 1
    new_keys = jnp.where(hot & ~is_query, keys ^ (salt << jnp.uint32(24)), keys)
    return new_keys, hot


# ----------------------------------------------------------------- reduce
def reduce_join(keys, payload, *, max_pairs: int):
    """Per-owner reduce: group by key, emit query x reference cross products.

    payload rows are (seq_id, is_query). Mirrors Algorithm 4 of the paper,
    vectorized: sort by (key, is_query) so queries precede references within
    a bucket, then for every reference row emit pairs against the bucket's
    query prefix.
    """
    EMPTY = jnp.uint32(0xFFFFFFFF)
    n = keys.shape[0]
    is_q = payload[:, 1] == 1
    # Sort by key, queries first inside each bucket (lexsort: last key primary).
    order = jnp.lexsort((jnp.where(is_q, 0, 1).astype(jnp.int32), keys))
    ks, ids, qflag = keys[order], payload[order, 0], is_q[order]
    valid = ks != EMPTY

    seg = jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    seg_id = jnp.cumsum(seg) - 1                       # (n,)
    # Number of queries in each bucket, and this row's bucket query offset.
    qcount_per_seg = jnp.zeros(n, jnp.int32).at[seg_id].add(
        (qflag & valid).astype(jnp.int32))
    nq = qcount_per_seg[seg_id]
    # Index of the first row of this row's bucket.
    seg_start_idx = jax.lax.cummax(jnp.where(seg, jnp.arange(n), 0), axis=0)
    # Each *reference* row emits nq pairs (its bucket's queries).
    emit_counts = jnp.where(valid & ~qflag, nq, 0)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(emit_counts)])
    total = offsets[-1]
    slots = jnp.arange(max_pairs, dtype=jnp.int32)
    row = jnp.searchsorted(offsets, slots, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, n - 1)
    j = slots - offsets[row]
    ok = slots < total
    q_idx = seg_start_idx[row] + j                     # queries sit at bucket head
    qid = jnp.where(ok, ids[jnp.clip(q_idx, 0, n - 1)], -1)
    rid = jnp.where(ok, ids[row], -1)
    pairs = jnp.stack([qid, rid], axis=-1).astype(jnp.int32)
    return pairs, total


# ----------------------------------------------------------------- engine
@dataclass(frozen=True)
class MapReduceConfig:
    n_shards: int
    shuffle_capacity: int = 4096     # records per (src,dst) shard pair
    max_pairs_per_shard: int = 8192
    hot_threshold: int = 64
    n_salt: int = 4
    salting: bool = True
    axis_name: str = "data"


def distributed_flip_join(q_sigs, r_sigs, q_ids, r_ids, *, f: int, d: int,
                          mesh, cfg: MapReduceConfig):
    """The paper's Signature Processor as a shard_map program.

    q_sigs/r_sigs: (Nq, 1)/(Nr, 1) uint32 (f <= 32), sharded on axis 0.
    q_ids/r_ids: global sequence ids (int32).
    Returns (pairs (n_shards, max_pairs, 2), counts, dropped) — host code
    concatenates valid rows; every emitted pair is exact-filtered by the
    caller (pairs carry ids, signatures are re-looked-up host-side).
    """
    from .join import flip_masks
    masks = jnp.asarray(flip_masks(f, d))[:, 0]        # (M,)
    M = int(masks.shape[0])
    ax = cfg.axis_name

    def shard_fn(qs, rs, qi, ri):
        # --- map phase: queries emit own key; refs emit M flipped keys.
        qk = qs[:, 0]
        rk = (rs[:, 0][:, None] ^ masks[None, :]).ravel()
        rid = jnp.repeat(ri, M)
        keys = jnp.concatenate([qk, rk])
        ids = jnp.concatenate([qi, rid])
        isq = jnp.concatenate(
            [jnp.ones_like(qi), jnp.zeros_like(rid)]).astype(jnp.int32)
        # Empty-slot convention: ids < 0 mark padding rows.
        EMPTY = jnp.uint32(0xFFFFFFFF)
        keys = jnp.where(ids >= 0, keys, EMPTY)
        if cfg.salting:
            keys, _ = salt_hot_keys(
                keys, hot_threshold=cfg.hot_threshold, n_salt=cfg.n_salt,
                is_query=isq == 1, replicate_queries=False)
            # Replicate each query record across all salts of its bucket.
            qkeys = keys[: qk.shape[0]]
            salts = (jnp.arange(cfg.n_salt, dtype=jnp.uint32) + 1) << jnp.uint32(24)
            qk_rep = (qkeys[:, None] ^ jnp.concatenate(
                [jnp.zeros(1, jnp.uint32), salts])[None, :]).ravel()
            qi_rep = jnp.repeat(qi, cfg.n_salt + 1)
            keys = jnp.concatenate([qk_rep, keys[qk.shape[0]:]])
            ids = jnp.concatenate([qi_rep, ids[qk.shape[0]:]])
            isq = jnp.concatenate(
                [jnp.ones_like(qi_rep), isq[qk.shape[0]:]]).astype(jnp.int32)
            keys = jnp.where(ids >= 0, keys, EMPTY)
        payload = jnp.stack([ids, isq], axis=-1)
        # --- shuffle phase.
        k2, p2, dropped = shuffle_records(
            keys, payload, axis_name=ax, n_shards=cfg.n_shards,
            capacity=cfg.shuffle_capacity)
        # --- reduce phase.
        pairs, total = reduce_join(k2, p2, max_pairs=cfg.max_pairs_per_shard)
        return pairs, total[None], dropped[None]

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax), P(ax)), check_vma=False,
    )
    return fn(q_sigs, r_sigs, q_ids, r_ids)


def ring_sweep(q_sigs, r_sigs, *, d: int, mesh, axis_name: str = "data",
               max_pairs_per_shard: int = 8192, q_ids=None, r_ids=None):
    """Streaming all-pairs sweep: reference blocks rotate around the ring via
    `ppermute` while each resident block is swept with the XOR+popcount
    distance — the comm/compute-overlap alternative to the shuffle join
    (DESIGN.md §5). Exact (no candidate generation).
    """
    n = mesh.shape[axis_name]

    def shard_fn(qs, rs, qi, ri):
        def step(carry, _):
            rblk, rids, pairs, cnt, hop = carry
            dist = jnp.sum(
                jax.lax.population_count(qs[:, None, :] ^ rblk[None, :, :]),
                axis=-1).astype(jnp.int32)
            hit = (dist <= d) & (qi[:, None] >= 0) & (rids[None, :] >= 0)
            # Compact hits into the fixed buffer at offset cnt.
            flat = hit.ravel()
            order = jnp.argsort(~flat, stable=True)[:max_pairs_per_shard]
            ok = flat[order]
            qq = qi[(order // rblk.shape[0]).astype(jnp.int32)]
            rr = rids[(order % rblk.shape[0]).astype(jnp.int32)]
            new = jnp.stack([jnp.where(ok, qq, -1), jnp.where(ok, rr, -1)], -1)
            nh = jnp.sum(ok.astype(jnp.int32))
            idx = jnp.arange(max_pairs_per_shard)
            write = (idx >= cnt) & (idx < cnt + nh)
            src = jnp.clip(idx - cnt, 0, max_pairs_per_shard - 1)
            pairs = jnp.where(write[:, None], new[src], pairs)
            cnt = cnt + nh
            # Rotate the reference block one hop around the ring (overlaps
            # with the next iteration's sweep under async dispatch).
            perm = [(i, (i + 1) % n) for i in range(n)]
            rblk = jax.lax.ppermute(rblk, axis_name, perm)
            rids = jax.lax.ppermute(rids, axis_name, perm)
            return (rblk, rids, pairs, cnt, hop + 1), None

        pairs0 = jnp.full((max_pairs_per_shard, 2), -1, jnp.int32)
        carry0 = (rs, ri, pairs0, jnp.int32(0), jnp.int32(0))
        (rs_f, ri_f, pairs, cnt, _), _ = jax.lax.scan(step, carry0, None, length=n)
        return pairs, cnt[None]

    if q_ids is None:
        q_ids = jnp.arange(q_sigs.shape[0], dtype=jnp.int32)
    if r_ids is None:
        r_ids = jnp.arange(r_sigs.shape[0], dtype=jnp.int32)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)), check_vma=False,
    )
    return fn(q_sigs, r_sigs, q_ids, r_ids)
