"""In-graph collective primitives over named mesh axes.

The reference exposes collectives as host-driven library calls dispatched
to NCCL/MPI/Gloo (reference: ops/collective_operations.h:38-276,
operations.cc:900-1188).  On TPU the idiomatic form is *in-graph*: these
wrappers are called inside ``shard_map``-decorated / pjit-compiled
functions, lower to XLA collective HLOs, and ride the ICI mesh.  The eager
API in :mod:`horovod_tpu.ops` builds fused batches out of exactly these
primitives.

Every function takes ``axis_name`` — one or more mesh axis names — the
analog of choosing a communicator.
"""

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisNames = Union[str, Sequence[str]]


def allreduce_sum(x: jax.Array, axis_name: AxisNames = "dp") -> jax.Array:
    """Sum-allreduce over mesh axis(es); lowers to a single XLA AllReduce."""
    return lax.psum(x, axis_name)


def allreduce_mean(x: jax.Array, axis_name: AxisNames = "dp") -> jax.Array:
    return lax.pmean(x, axis_name)


def allreduce_min(x: jax.Array, axis_name: AxisNames = "dp") -> jax.Array:
    return -lax.pmax(-x, axis_name)


def allreduce_max(x: jax.Array, axis_name: AxisNames = "dp") -> jax.Array:
    return lax.pmax(x, axis_name)


def allreduce_prod(x: jax.Array, axis_name: AxisNames = "dp") -> jax.Array:
    # XLA has no product allreduce primitive; use exp/log for positive
    # values is lossy, so go through all_gather + reduce instead.
    gathered = lax.all_gather(x, axis_name)
    return jnp.prod(gathered, axis=0)


def allgather(x: jax.Array, axis_name: AxisNames = "dp",
              axis: int = 0, tiled: bool = True) -> jax.Array:
    """Gather shards from all members along ``axis``.

    ``tiled=True`` concatenates along ``axis`` (Horovod allgather
    semantics: rank outputs stacked on dim 0, reference
    ops/collective_operations.cc allgather offset math); ``tiled=False``
    adds a new leading axis.
    """
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis_name: AxisNames = "dp",
                   axis: int = 0) -> jax.Array:
    """Sum then scatter shards along ``axis`` (ZeRO/FSDP workhorse).

    Exposed as a public op — the reference only uses reduce-scatter
    internally inside hierarchical allreduce (SURVEY §2.3); on TPU it is
    first-class because reduce-scatter + allgather is how both
    hierarchical allreduce and FSDP lower.
    """
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x: jax.Array, root_rank: int = 0,
              axis_name: AxisNames = "dp") -> jax.Array:
    """Broadcast ``root_rank``'s value to all members of the axis.

    Lowered as a select + psum so XLA emits an efficient collective; this
    is the standard TPU idiom (no dedicated broadcast HLO over mesh axes).
    """
    idx = lax.axis_index(axis_name)
    zeros = jnp.zeros_like(x)
    masked = jnp.where(idx == root_rank, x, zeros)
    return lax.psum(masked, axis_name)


def alltoall(x: jax.Array, axis_name: AxisNames = "dp",
             split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """Even all-to-all: split dim `split_axis` across the axis members and
    concatenate received chunks along ``concat_axis``.

    This is the Ulysses sequence-parallel / MoE expert-parallel primitive
    (the reference added alltoall for exactly these workloads,
    operations.cc:1099-1160).
    """
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def alltoallv(x: jax.Array, send_counts: jax.Array,
              axis_name: AxisNames = "dp") -> jax.Array:
    """Uneven all-to-all emulation (reference alltoall with splits,
    collective_operations.h:206-256).

    XLA's all_to_all is even-only; uneven splits are handled by padding
    each chunk to the max count, exchanging, then callers slice with the
    received counts (which are exchanged alongside as a tiny alltoall).
    Returns the padded exchanged buffer plus received counts.
    """
    n = lax.psum(1, axis_name)
    # Exchange counts first (tiny, rides the same compiled program).
    recv_counts = lax.all_to_all(
        send_counts.reshape(n, 1), axis_name, split_axis=0, concat_axis=0,
        tiled=True).reshape(n)
    return x, recv_counts  # caller handles padding layout


def ppermute(x: jax.Array, perm, axis_name: AxisNames = "dp") -> jax.Array:
    """Point-to-point permutation — building block for rings (ring
    attention, Adasum VHDD ladders)."""
    return lax.ppermute(x, axis_name, perm)


def neighbor_shift(x: jax.Array, shift: int = 1,
                   axis_name: AxisNames = "dp") -> jax.Array:
    """Cyclic shift by ``shift`` along the axis ring (ICI-neighbor move)."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: AxisNames = "dp") -> jax.Array:
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisNames = "dp") -> int:
    return lax.psum(1, axis_name)


def hierarchical_allreduce_sum(x: jax.Array, local_axis: str = "local",
                               cross_axis: str = "cross") -> jax.Array:
    """Reduce-scatter over ICI → allreduce over DCN → allgather over ICI.

    The TPU mapping of the reference's NCCLHierarchicalAllreduce
    (ops/nccl_operations.cc:188-360: NCCL ReduceScatter → cross-node
    MPI_Allreduce → NCCL Allgather).  On flat meshes XLA would fuse a
    plain psum over both axes anyway; this explicit form matters when the
    cross axis is DCN and we want the DCN transfer to be 1/local_size the
    size.
    """
    orig_shape = x.shape
    flat = x.reshape(-1)
    n_local = lax.psum(1, local_axis)
    pad = (-flat.shape[0]) % n_local
    flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)
    shard = lax.psum(shard, cross_axis)
    full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(orig_shape)
