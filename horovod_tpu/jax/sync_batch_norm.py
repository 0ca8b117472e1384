"""Synchronized BatchNorm for the JAX binding.

Two idioms, matching the two training paths:

* **Compiled/SPMD path** — :func:`SyncBatchNorm` returns a
  ``flax.linen.BatchNorm`` configured with ``axis_name``: flax computes
  batch statistics with ``lax.pmean`` over the mesh axis inside the
  compiled program (this IS the stacked-moment allreduce of the
  reference, tensorflow/sync_batch_norm.py:26-60, fused by XLA).
* **Eager/hook path** — :func:`sync_batch_stats` allreduces a
  ``batch_stats`` collection between steps, the way the reference's
  torch/TF bindings synchronize moving statistics.
"""

from typing import Any, Optional

import jax
import numpy as np

from ..common.basics import Average, global_process_set
from .. import ops as _ops


def SyncBatchNorm(use_running_average: Optional[bool] = None,
                  axis_name: str = "dp", momentum: float = 0.9,
                  epsilon: float = 1e-5, **kwargs):
    """A flax BatchNorm whose batch statistics reduce over
    ``axis_name`` (call inside shard_map/pjit over the mesh)."""
    import flax.linen as nn
    return nn.BatchNorm(use_running_average=use_running_average,
                        axis_name=axis_name, momentum=momentum,
                        epsilon=epsilon, **kwargs)


def sync_batch_stats(batch_stats: Any,
                     process_set=global_process_set) -> Any:
    """Average a ``batch_stats`` pytree (running mean/var) across ranks
    through the eager runtime."""
    leaves, treedef = jax.tree_util.tree_flatten(batch_stats)
    out = []
    for i, leaf in enumerate(leaves):
        out.append(_ops.allreduce(np.asarray(leaf), op=Average,
                                  name=f"sync_bn_stats/{i}",
                                  process_set=process_set))
    return jax.tree_util.tree_unflatten(treedef, out)
