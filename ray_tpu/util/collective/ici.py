"""In-graph (ICI) collectives: XLA ops over a device mesh.

On TPU the intra-slice fabric is only reachable from inside compiled
programs — there is no host-initiated NCCL analog. These helpers wrap the
XLA collectives (`psum`, `all_gather`, `ppermute`, `psum_scatter`) in
`shard_map` over a :class:`jax.sharding.Mesh` so callers get an
imperative-looking API whose body compiles to ICI traffic.

This is the TPU replacement for the reference's NCCLGroup
(/root/reference/python/ray/util/collective/collective_group/
nccl_collective_group.py:127): the reference moves GPU tensors with NCCL
from the host; we stage arrays once and let XLA schedule the transfer.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def allreduce(x: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Allreduce an array whose leading dim is sharded over ``axis``;
    every shard ends up holding the sum of all shards."""
    spec = P(axis)

    @functools.partial(_shard_map, mesh=mesh, check_vma=False, in_specs=spec, out_specs=spec)
    def _ar(shard):
        total = jax.lax.psum(shard.sum(axis=0, keepdims=True), axis)
        return jnp.broadcast_to(total, shard.shape)

    return jax.jit(_ar)(x)


def psum(x: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Sum replicated-per-device values over the mesh axis; returns the
    reduced value replicated everywhere (classic gradient allreduce)."""

    @functools.partial(
        _shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P())
    def _psum(shard):
        return jax.lax.psum(shard, axis)

    n = mesh.shape[axis]
    stacked = x if x.shape and x.shape[0] == n else \
        jnp.broadcast_to(x[None], (n,) + x.shape)
    return jax.jit(_psum)(stacked)


def all_gather(x: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Gather shards along the leading dim onto every device."""
    @functools.partial(
        _shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P())
    def _ag(shard):
        return jax.lax.all_gather(shard, axis, axis=0, tiled=True)

    return jax.jit(_ag)(x)


def reduce_scatter(x: jax.Array, mesh: Mesh,
                   axis: str = "data") -> jax.Array:
    """Treat each device's shard (leading dim 1 of an ``axis``-sharded
    array) as its contribution; elementwise-reduce the contributions and
    leave each device with its 1/N piece of the sum. The contribution size
    must be divisible by the axis size."""
    @functools.partial(
        _shard_map, mesh=mesh, check_vma=False, in_specs=P(axis),
        out_specs=P(axis))
    def _rs(shard):
        flat = shard.reshape((-1,))
        piece = jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                     tiled=True)
        return piece[None]

    return jax.jit(_rs)(x)


def ppermute(x: jax.Array, mesh: Mesh, axis: str = "data",
             shift: int = 1) -> jax.Array:
    """Neighbor exchange around the ring (the building block of ring
    attention / pipeline transfers)."""
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @functools.partial(
        _shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P(axis))
    def _pp(shard):
        return jax.lax.ppermute(shard, axis, perm)

    return jax.jit(_pp)(x)


def device_put_sharded(x, mesh: Mesh, axis: Optional[str] = "data"):
    """Stage a host array onto the mesh, sharded along the leading dim."""
    spec = P(axis) if axis else P()
    return jax.device_put(x, NamedSharding(mesh, spec))
