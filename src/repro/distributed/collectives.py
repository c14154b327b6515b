"""Collective-overlap utilities.

XLA already overlaps collectives with independent compute where the
schedule allows; these helpers create the *opportunity*:

* ``ring_allreduce`` — reduce-scatter + all-gather via ppermute, in
  ``chunks`` pipeline stages.  Splitting one big psum into chunked
  permutes lets the compiler interleave chunk k's compute with chunk
  k+1's transfer (the classic bucketed-allreduce overlap).  Used by the
  perf experiments to measure collective-schedule alternatives against
  stock psum.
* ``psum_in_chunks`` — simple bucketing of a gradient tree so parameter
  updates for early buckets can start while later buckets still reduce.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def replica_groups(devices, num_replicas: int):
    """Partition ``devices`` into ``num_replicas`` contiguous equal groups.

    The serving mesh's layout: replica r owns ``devices[r*g:(r+1)*g]``
    (g = len(devices) // num_replicas).  A group of one device holds a
    plain replicated program; a larger group shards one program across its
    members (model too large for one device).  Contiguity keeps each
    group's collectives on neighbouring devices.
    """
    if num_replicas < 1:
        raise ValueError(f"num_replicas must be >= 1, got {num_replicas}")
    devices = list(devices)
    if len(devices) < num_replicas:
        raise ValueError(
            f"{len(devices)} device(s) cannot host {num_replicas} replicas"
        )
    group = len(devices) // num_replicas
    return [devices[r * group : (r + 1) * group] for r in range(num_replicas)]


def ring_allreduce(x: jnp.ndarray, axis_name: str, chunks: int | None = None) -> jnp.ndarray:
    """Ring all-reduce over ``axis_name`` (use inside shard_map).

    Equivalent to lax.psum but expressed as 2(P-1) ppermute steps over
    1/P-sized chunks — the canonical bandwidth-optimal schedule, and a
    form XLA can overlap with compute chunk-by-chunk.
    """
    p = jax.lax.axis_size(axis_name)
    if p == 1:
        return x
    me = jax.lax.axis_index(axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        flat = jnp.pad(flat, (0, pad))
    parts = flat.reshape(p, -1)

    perm_fwd = [(i, (i + 1) % p) for i in range(p)]

    # reduce-scatter: after P-1 steps, rank r holds the full sum of part
    # (r+1) mod p.
    def rs_step(i, parts):
        send_idx = (me - i) % p
        chunk = jnp.take(parts, send_idx, axis=0)
        received = jax.lax.ppermute(chunk, axis_name, perm_fwd)
        recv_idx = (me - i - 1) % p
        return parts.at[recv_idx].add(received)

    parts = jax.lax.fori_loop(0, p - 1, rs_step, parts)

    # all-gather the reduced chunks around the ring.
    def ag_step(i, parts):
        send_idx = (me + 1 - i) % p
        chunk = jnp.take(parts, send_idx, axis=0)
        received = jax.lax.ppermute(chunk, axis_name, perm_fwd)
        recv_idx = (me - i) % p
        return parts.at[recv_idx].set(received)

    parts = jax.lax.fori_loop(0, p - 1, ag_step, parts)
    out = parts.reshape(-1)
    if pad:
        out = out[: flat.size - pad] if pad else out
        out = out[: x.size]
    return out[: x.size].reshape(orig_shape)


def psum_in_chunks(tree, axis_name: str, num_buckets: int = 4):
    """Reduce a gradient tree in ``num_buckets`` separate psums so the
    compiler can overlap buckets with downstream per-bucket updates."""
    leaves, treedef = jax.tree.flatten(tree)
    buckets: list[list[int]] = [[] for _ in range(num_buckets)]
    sizes = [0] * num_buckets
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i].size)
    for i in order:  # greedy size balancing
        b = sizes.index(min(sizes))
        buckets[b].append(i)
        sizes[b] += leaves[i].size
    out: list = [None] * len(leaves)
    for bucket in buckets:
        if not bucket:
            continue
        reduced = jax.lax.psum(tuple(leaves[i] for i in bucket), axis_name)
        for i, r in zip(bucket, reduced):
            out[i] = r
    return jax.tree.unflatten(treedef, out)
