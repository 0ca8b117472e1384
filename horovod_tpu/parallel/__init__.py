from .mesh import (build_hierarchical_mesh, build_mesh, local_mesh,
                   mesh_axis_size, parse_mesh_axes, replicated, sharded)
from .collectives import (allgather, allreduce_max, allreduce_mean,
                          allreduce_min, allreduce_prod, allreduce_sum,
                          alltoall, axis_index, axis_size, broadcast,
                          hierarchical_allreduce_sum, neighbor_shift,
                          ppermute, reduce_scatter)

__all__ = [
    "build_mesh", "build_hierarchical_mesh", "local_mesh", "sharded",
    "replicated", "mesh_axis_size", "parse_mesh_axes",
    "allreduce_sum", "allreduce_mean", "allreduce_min", "allreduce_max",
    "allreduce_prod", "allgather", "reduce_scatter", "broadcast",
    "alltoall", "ppermute", "neighbor_shift", "axis_index", "axis_size",
    "hierarchical_allreduce_sum",
]

from .attention import (reference_attention, ring_attention,
                        ulysses_attention)
__all__ += ["ring_attention", "ulysses_attention", "reference_attention"]
