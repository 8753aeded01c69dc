"""shard_map-level collectives: the distributed queue's aggregation
primitives and the compressed/overlapped data-parallel gradient sync.

These are the TPU-idiomatic renderings of the paper's coordination patterns
(DESIGN.md § 2.3): contention aggregation becomes an exclusive prefix sum
over the mesh axis (one collective round ≡ one wave-batched FAA), and the
cross-pod gradient all-reduce supports int8 error-feedback compression and
bucketed issue so communication overlaps the remaining backward compute.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import compression


# ---------------------------------------------------------------------------
# hierarchical ticket aggregation (the cross-chip WAVEFAA)
# ---------------------------------------------------------------------------


def mesh_ticket_base(count: jax.Array, axis: str) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: every participant contributes its request count;
    returns (exclusive prefix over the axis = this shard's ticket base,
    total).  One collective round hands out globally unique, ordered ticket
    blocks — the paper's leader-FAA one level up the hierarchy."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n,), 0) == idx)
    contrib = jnp.where(onehot, count, 0)
    sums = jax.lax.psum(contrib, axis)              # (n,) per-shard counts
    base = jnp.sum(jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n,), 0) < idx,
                             sums, 0))
    return base, jnp.sum(sums)


def mesh_round_gather(blocks, axis: str):
    """Replicated gather of per-shard compact blocks in ONE psum.

    ``blocks`` is a tuple of (B_i,) int32 arrays (one round's local op
    payloads — values, masks, …).  Every shard scatters its concatenated
    blocks into its row of an (n, ΣB_i) zero buffer and the buffer is
    psum-reduced: each row has exactly one contributor, so the reduction is
    a bit-exact integer gather, and — unlike ``all_gather``, whose output
    shard_map's varying-manual-axes checker types as device-varying — the
    psum output is *replicated-typed*.  This is what lets the distqueue
    round state keep its ``P()`` out_spec with the checker on
    (``check_vma=True``).  Returns (n, B_i)-shaped arrays, one per block.
    Per-shard counts/ticket bases fall out of the gathered masks (a cumsum),
    so one call subsumes ``mesh_ticket_base`` + payload exchange — the whole
    round costs this single collective."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    widths = [int(b.shape[-1]) for b in blocks]
    row = jnp.concatenate([b.astype(jnp.int32) for b in blocks])
    buf = jnp.zeros((n, sum(widths)), jnp.int32).at[me].set(row)
    out = jax.lax.psum(buf, axis)
    split, off = [], 0
    for w in widths:
        split.append(out[:, off:off + w])
        off += w
    return tuple(split)


# ---------------------------------------------------------------------------
# compressed / bucketed gradient all-reduce (cross-pod DP)
# ---------------------------------------------------------------------------


def allreduce_mean(x: jax.Array, axis: str) -> jax.Array:
    return jax.lax.pmean(x, axis)


def allreduce_compressed(g: jax.Array, err: jax.Array, axis: str):
    """Error-feedback int8 all-reduce: quantize locally, mean-reduce the
    dequantized payload (the wire format is int8 + per-block scales — XLA
    reduces the dequantized f32 here; payload accounting uses
    ``compression.compression_ratio``), return (reduced, new_err)."""
    deq, new_err = compression.compress_with_feedback(g, err)
    return jax.lax.pmean(deq, axis), new_err


def tree_allreduce_compressed(grads: Any, errs: Any, axis: str):
    out = jax.tree.map(lambda g, e: allreduce_compressed(g, e, axis),
                       grads, errs)
    red = jax.tree.map(lambda p: p[0], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    new = jax.tree.map(lambda p: p[1], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    return red, new


def bucketed_psum(leaves, axis: str, bucket_bytes: int = 1 << 25):
    """Issue psums in buckets (≈32 MiB) so each starts as soon as its
    gradients are ready — compute/communication overlap on the backward
    pass.  Returns reduced leaves in the original order."""
    order = sorted(range(len(leaves)), key=lambda i: leaves[i].size)
    out = [None] * len(leaves)
    bucket, bucket_sz = [], 0
    for i in order:
        bucket.append(i)
        bucket_sz += leaves[i].size * leaves[i].dtype.itemsize
        if bucket_sz >= bucket_bytes:
            red = jax.lax.psum(tuple(leaves[j] for j in bucket), axis)
            for j, r in zip(bucket, red):
                out[j] = r
            bucket, bucket_sz = [], 0
    if bucket:
        red = jax.lax.psum(tuple(leaves[j] for j in bucket), axis)
        for j, r in zip(bucket, red):
            out[j] = r
    return out
