"""Snow collectives: tree broadcast / reduce / all-reduce as
``lax.ppermute`` schedules inside ``shard_map``.

These implement the paper's dissemination pattern on the data plane:

* ``snow_broadcast``  — the §4.2 k-ary balanced tree, O(k·log_k P)
  ppermute rounds; latency-optimal for small payloads vs the ring's
  O(P) hops (the cross-pod / DCN regime Snow targets).
* ``snow_reduce``     — the Reliable-Message ACK path (§4.4) run in
  reverse with payload aggregation.
* ``snow_allreduce``  — reduce-to-root + broadcast.
* ``two_tree_broadcast`` — Coloring (§4.6): payload split in half, one
  half per tree; internal nodes of one tree are leaves of the other
  (Appendix C), so both halves stream at full fan-out bandwidth — the
  SplitStream-style option the paper sketches.

All functions are *inside-shard_map* collectives: they take the mapped
view of an array and an axis name.  ``*_spmd`` wrappers apply them to a
replicated array over a mesh axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .topology import (broadcast_schedule, reduce_schedule,
                       two_tree_schedules)


def snow_broadcast(x: jax.Array, axis_name: str, *, axis_size: int,
                   root: int = 0, k: int = 2) -> jax.Array:
    """Tree-broadcast the root's value to every device on the axis."""
    idx = lax.axis_index(axis_name)
    for rnd in broadcast_schedule(axis_size, root, k):
        y = lax.ppermute(x, axis_name, perm=list(rnd))
        is_dst = functools.reduce(
            jnp.logical_or, [idx == d for _, d in rnd], jnp.bool_(False))
        x = jnp.where(is_dst, y, x)
    return x


def snow_reduce(x: jax.Array, axis_name: str, *, axis_size: int,
                root: int = 0, k: int = 2) -> jax.Array:
    """Sum-reduce to the root along the reversed tree (ACK path)."""
    idx = lax.axis_index(axis_name)
    for rnd in reduce_schedule(axis_size, root, k):
        y = lax.ppermute(x, axis_name, perm=list(rnd))
        is_dst = functools.reduce(
            jnp.logical_or, [idx == d for _, d in rnd], jnp.bool_(False))
        x = x + jnp.where(is_dst, y, jnp.zeros_like(y))
    return x


def snow_allreduce(x: jax.Array, axis_name: str, *, axis_size: int,
                   root: int = 0, k: int = 2) -> jax.Array:
    x = snow_reduce(x, axis_name, axis_size=axis_size, root=root, k=k)
    return snow_broadcast(x, axis_name, axis_size=axis_size, root=root, k=k)


def two_tree_broadcast(x: jax.Array, axis_name: str, *, axis_size: int,
                       root: int = 0, k: int = 2) -> jax.Array:
    """Coloring broadcast: halves of the payload travel down the two
    internal-node-disjoint trees concurrently (§4.6, Appendix D)."""
    idx = lax.axis_index(axis_name)
    sched_p, sched_s = two_tree_schedules(axis_size, root, k)
    flat = x.reshape(-1)
    pad = (-flat.size) % 2
    if pad:
        flat = jnp.pad(flat, (0, pad))
    halves = list(jnp.split(flat, 2))
    for hi, sched in ((0, sched_p), (1, sched_s)):
        h = halves[hi]
        for rnd in sched:
            y = lax.ppermute(h, axis_name, perm=list(rnd))
            is_dst = functools.reduce(
                jnp.logical_or, [idx == d for _, d in rnd], jnp.bool_(False))
            h = jnp.where(is_dst, y, h)
        halves[hi] = h
    out = jnp.concatenate(halves)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


# --------------------------------------------------------------------- #
# SPMD wrappers (operate on mesh-replicated arrays)                      #
# --------------------------------------------------------------------- #
def _spmd(fn, mesh: Mesh, axis_name: str, **kw):
    # in/out replicated w.r.t. the mesh: each device owns a full copy and
    # the tree schedule moves it; check_vma off because replication of
    # the output is a property of the schedule, not provable by types.
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False)
    def run(x):
        return fn(x, axis_name, axis_size=mesh.shape[axis_name], **kw)

    return run


def snow_broadcast_spmd(x, mesh: Mesh, axis_name: str, *, root: int = 0,
                        k: int = 2):
    return _spmd(snow_broadcast, mesh, axis_name, root=root, k=k)(x)


def snow_allreduce_spmd(x, mesh: Mesh, axis_name: str, *, root: int = 0,
                        k: int = 2):
    return _spmd(snow_allreduce, mesh, axis_name, root=root, k=k)(x)


def two_tree_broadcast_spmd(x, mesh: Mesh, axis_name: str, *, root: int = 0,
                            k: int = 2):
    return _spmd(two_tree_broadcast, mesh, axis_name, root=root, k=k)(x)
