"""Device-mesh construction for TPU slices.

This replaces the reference's communicator plumbing (reference:
common/mpi/mpi_context.h:42-91 builds global/local/cross MPI communicators;
common/gloo/gloo_context.cc:121-216 builds the same trio over TCP) with the
TPU-native equivalent: a named `jax.sharding.Mesh` whose axes are laid out
so collectives ride ICI within a slice and DCN across slices.

Axis conventions used throughout horovod_tpu:

- ``dp``  — data parallel (gradient allreduce axis)
- ``fsdp`` — fully-sharded data parallel (parameter/optimizer sharding)
- ``tp``  — tensor/model parallel
- ``sp``  — sequence/context parallel (ring attention / Ulysses)
- ``ep``  — expert parallel (MoE all-to-all)
- ``pp``  — pipeline parallel
- ``cross`` / ``local`` — the 2-level hierarchy used by hierarchical
  collectives (DCN leg / ICI leg), mirroring the reference's
  cross_comm / local_comm split.
"""

import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import env as env_mod

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_AXES = ("dp", "fsdp", "tp", "sp", "ep")


def _factor(n: int, shape: Sequence[int]) -> List[int]:
    """Fill in at most one -1 in `shape` so the product equals n."""
    shape = list(shape)
    if shape.count(-1) > 1:
        raise ValueError("at most one -1 allowed in mesh shape")
    known = math.prod(s for s in shape if s != -1)
    if -1 in shape:
        if n % known != 0:
            raise ValueError(f"cannot factor {n} devices into shape {shape}")
        shape[shape.index(-1)] = n // known
    elif known != n:
        raise ValueError(f"mesh shape {shape} does not cover {n} devices")
    return shape


def parse_mesh_axes(spec: str) -> Dict[str, int]:
    """Parse a ``HOROVOD_TPU_MESH_AXES`` spec like ``"dp:4,tp:2"``."""
    axes: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition(":")
        axes[name.strip()] = int(size) if size else -1
    return axes


def build_mesh(axis_sizes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence[jax.Device]] = None,
               *, allow_split_physical_axes: bool = True) -> Mesh:
    """Build a named device mesh.

    With no arguments this produces a 1-D data-parallel mesh over every
    addressable device — the direct analog of the reference's default
    world communicator.  ``axis_sizes`` may contain a single ``-1`` which
    absorbs the remaining device count.

    On real TPU slices ``jax.experimental.mesh_utils`` is used so the axis
    order maps contiguous ICI neighborhoods to the innermost axes (the
    scaling-book recipe: put the heavy-traffic axis on ICI); a shape it
    cannot lay out on the slice raises.  Off the TPU, and for a single
    device, the order is row-major.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axis_sizes:
        spec = env_mod.env_str_opt(env_mod.HOROVOD_TPU_MESH_AXES)
        axis_sizes = parse_mesh_axes(spec) if spec else {"dp": n}
    names = tuple(axis_sizes.keys())
    shape = _factor(n, list(axis_sizes.values()))

    if devices[0].platform == "tpu" and n > 1:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            tuple(shape), devices=devices,
            allow_split_physical_axes=allow_split_physical_axes)
        return Mesh(dev_array, names)
    return Mesh(np.array(devices).reshape(tuple(shape)), names)


def build_hierarchical_mesh(
        devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-level (cross, local) mesh mirroring cross_comm x local_comm.

    ``local`` groups devices sharing a host/process (ICI-adjacent on TPU);
    ``cross`` spans hosts (DCN).  Hierarchical allreduce lowers to
    reduce-scatter over ``local`` → allreduce over ``cross`` → allgather
    over ``local``, the same split as the reference's
    NCCLHierarchicalAllreduce (ops/nccl_operations.cc:188-360).
    """
    devices = list(devices if devices is not None else jax.devices())
    by_proc: Dict[int, List[jax.Device]] = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    counts = {len(v) for v in by_proc.values()}
    if len(counts) != 1:
        # Heterogeneous device counts: degrade to a flat mesh.
        return Mesh(np.array(devices).reshape(1, -1), ("cross", "local"))
    local = counts.pop()
    rows = [by_proc[k] for k in sorted(by_proc)]
    return Mesh(np.array(rows), ("cross", "local"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def local_mesh(axis_name: str = "dp") -> Mesh:
    """1-D mesh over this process's local devices only."""
    return Mesh(np.array(jax.local_devices()), (axis_name,))
