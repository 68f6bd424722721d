"""Collective transfer programs — the ICI data plane.

This is the TPU-native replacement for the reference's RDMA endpoint
(src/brpc/rdma/rdma_endpoint.h) AND its combo-channel parallelism layer
(SURVEY.md §2.11): instead of N sockets carrying scattered sub-requests, one
compiled XLA program moves the same traffic over ICI:

- ParallelChannel broadcast + ResponseMerger  →  fanout_gather / fanout_reduce
  (parallel_channel.h:218 AddChannel/CallMapper/ResponseMerger)
- PartitionChannel "N/M" sharding             →  shard_apply (tensor-sharded
  server state, partial results merged by psum)
- Streaming RPC's windowed relay              →  ring_stream (ppermute ring,
  hop-by-hop like stream_impl.h's ordered ExecutionQueue delivery)
- pipelined connections                       →  all_to_all resharding

All programs are shard_map'ed over an explicit Mesh and jitted once; XLA
inserts the ICI collectives (psum/all_gather/ppermute) the way the
reference's KeepWrite pushed bytes into verbs queues.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from brpc_tpu.parallel.mesh import CLIENT_AXIS, SHARD_AXIS


def fanout_gather(mesh: Mesh, axis: str = SHARD_AXIS):
    """Broadcast-style fan-out, every shard returns its piece, caller gets
    the merged (concatenated) responses — ParallelChannel with a
    concatenating ResponseMerger."""

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P())
    def _gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    return jax.jit(_gather)


def fanout_reduce(mesh: Mesh, axis: str = CLIENT_AXIS):
    """Fan-out with a summing ResponseMerger: every client shard contributes,
    all see the reduced result (gradient aggregation shape)."""

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P())
    def _reduce(x):
        return jax.lax.psum(x, axis)

    return jax.jit(_reduce)


def reduce_scatter(mesh: Mesh, axis: str = CLIENT_AXIS):
    """Sum contributions but leave the result sharded — the bandwidth-optimal
    half of fanout_reduce (merge once, deliver shard-local)."""

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P(axis))
    def _rs(x):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)

    return jax.jit(_rs)


def ring_stream(mesh: Mesh, hops: int = 1, axis: str = SHARD_AXIS):
    """Move each shard's block `hops` steps around the ring — the streaming
    tensor relay (chunk k of the stream lives on device (i+k) % n after k
    ticks, the ppermute pipeline every ring-based transfer builds on)."""
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P(axis))
    def _stream(x):
        for _ in range(hops):
            x = jax.lax.ppermute(x, axis, perm)
        return x

    return jax.jit(_stream)


def all_to_all_reshard(mesh: Mesh, axis: str = SHARD_AXIS):
    """Repartition: each shard splits its block N ways and trades pieces —
    DynamicPartitionChannel's regrouping (partition_channel.h:136) as one
    collective."""

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=P(axis), out_specs=P(axis))
    def _a2a(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0,
                                  tiled=True)

    return jax.jit(_a2a)
