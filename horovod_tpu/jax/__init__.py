"""JAX framework binding — the flagship binding of horovod_tpu.

Usage mirrors the reference's per-framework modules (reference:
horovod/tensorflow/__init__.py, horovod/torch/__init__.py):

    import horovod_tpu.jax as hvd
    hvd.init()
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))

Two training paths:

* **Eager/hook path (this module)** — drop-in Horovod semantics: each
  gradient pytree is allreduced through the background runtime
  (negotiation + fusion + response cache), matching the reference
  DistributedOptimizer contract.
* **Compiled SPMD path** (:mod:`horovod_tpu.training`) — the full-
  performance path where the train step is jit-compiled over the mesh
  and XLA fuses the gradient reduction into the step program.

For use *inside* jit/shard_map, the in-graph primitives are re-exported
from :mod:`horovod_tpu.parallel`; build the enclosing program with
``jax.shard_map``.
"""

import pickle
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
import optax

from ..common import basics
from ..common import timeline as tl
from ..common.basics import (Adasum, Average, Max, Min, Product, Sum,
                             ProcessSet, global_process_set, init,
                             is_initialized, local_rank, local_size,
                             rank, shutdown, size)
from ..ops import (allgather, allgather_async, allreduce, allreduce_async,
                   alltoall, alltoall_async, barrier, broadcast,
                   broadcast_async, grouped_allreduce,
                   grouped_allreduce_async, join, poll, reducescatter,
                   synchronize)
from ..ops.compression import Compression
from .. import parallel
from . import checkpoint

__all__ = [
    "init", "shutdown", "rank", "size", "local_rank", "local_size",
    "is_initialized", "allreduce", "allreduce_async", "grouped_allreduce",
    "grouped_allreduce_async", "allgather", "allgather_async", "alltoall",
    "alltoall_async", "broadcast", "broadcast_async", "reducescatter",
    "join", "barrier", "poll", "synchronize", "Compression",
    "Average", "Sum", "Adasum", "Min", "Max", "Product",
    "allreduce_gradients", "DistributedOptimizer", "broadcast_parameters",
    "broadcast_optimizer_state", "broadcast_object", "allgather_object",
    "metric_average", "parallel",
]


def _tree_names(tree, prefix: str) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = []
    for path, _leaf in flat:
        parts = []
        for p in path:
            key = getattr(p, "key", getattr(p, "idx", getattr(p, "name",
                                                              None)))
            parts.append(str(key))
        names.append(prefix + "/" + "/".join(parts))
    return names


def allreduce_gradients(grads, op=Average, compression=Compression.none,
                        name_prefix: str = "grad",
                        process_set: ProcessSet = global_process_set):
    """Allreduce a gradient pytree through the background runtime as one
    fused group (reference analog: _make_allreduce_grads_fn,
    tensorflow/__init__.py:334-381).  One ``hvd/exchange`` span holds
    the ``hvd/submit`` and ``hvd/wait`` of every leaf."""
    with tl.span("exchange", prefix=name_prefix):
        return _allreduce_gradients(grads, op, compression, name_prefix,
                                    process_set)


def _allreduce_gradients(grads, op, compression, name_prefix, process_set):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    names = _tree_names(grads, name_prefix)
    compressed, ctxs = [], []
    for leaf in leaves:
        c, ctx = compression.compress(leaf)
        compressed.append(c)
        ctxs.append(ctx)
    handles = []
    for t, n in zip(compressed, names):
        handles.append(allreduce_async(t, name=n, op=op,
                                       process_set=process_set))
    reduced = [h.wait() for h in handles]
    restored = [compression.decompress(t, ctx)
                for t, ctx in zip(reduced, ctxs)]
    return jax.tree_util.tree_unflatten(treedef, restored)


class _AccumState:
    """Host-side accumulation for backward_passes_per_step (the local
    gradient aggregation of reference gradient_aggregation.py /
    torch/optimizer.py:71-73)."""

    def __init__(self, n: int):
        self.n = n
        self.counter = 0
        self.acc = None


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         compression=Compression.none,
                         op=Average,
                         backward_passes_per_step: int = 1,
                         name_prefix: str = "grad",
                         process_set: ProcessSet = global_process_set
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so every ``update`` first allreduces the
    gradients across the world (reference: DistributedOptimizer,
    tensorflow/__init__.py:568-689).

    With ``backward_passes_per_step > 1`` gradients are accumulated
    locally and only every Nth call triggers communication (scaled by
    1/N).  The wrapper drives the eager runtime and must therefore be
    stepped OUTSIDE jit; for fully-compiled training use
    horovod_tpu.training / horovod_tpu.parallel instead.
    """
    accum = _AccumState(backward_passes_per_step)

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(grads, state, params=None, **extra):
        if accum.n > 1:
            if accum.acc is None:
                accum.acc = grads
            else:
                accum.acc = jax.tree.map(jnp.add, accum.acc, grads)
            accum.counter += 1
            if accum.counter < accum.n:
                zero = jax.tree.map(jnp.zeros_like, grads)
                return zero, state
            grads = jax.tree.map(lambda g: g / accum.n, accum.acc)
            accum.acc, accum.counter = None, 0
        grads = allreduce_gradients(grads, op=op, compression=compression,
                                    name_prefix=name_prefix,
                                    process_set=process_set)
        return optimizer.update(grads, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)


def broadcast_parameters(params, root_rank: int = 0,
                         name_prefix: str = "param",
                         process_set: ProcessSet = global_process_set):
    """Broadcast a parameter pytree from ``root_rank`` (reference:
    torch/functions.py:29-67 broadcast_parameters /
    tensorflow broadcast_global_variables)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    names = _tree_names(params, name_prefix)
    handles = [broadcast_async(t, root_rank=root_rank, name=n,
                               process_set=process_set)
               for t, n in zip(leaves, names)]
    out = [h.wait() for h in handles]
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set: ProcessSet = global_process_set):
    """Broadcast optax optimizer state (reference:
    torch/functions.py:69-184 broadcast_optimizer_state)."""
    return broadcast_parameters(opt_state, root_rank,
                                name_prefix="opt_state",
                                process_set=process_set)


def broadcast_object(obj: Any = None, root_rank: int = 0,
                     name: str = "broadcast_object",
                     process_set: ProcessSet = global_process_set) -> Any:
    """Broadcast an arbitrary picklable object (reference:
    torch/functions.py:186-228 — cloudpickle → ByteTensor → broadcast
    size then payload)."""
    if basics.rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
        length = np.array([payload.size], dtype=np.int64)
    else:
        payload = None
        length = np.zeros(1, dtype=np.int64)
    length = np.asarray(broadcast(length, root_rank, name=f"{name}.len",
                                  process_set=process_set))
    if basics.rank() != root_rank:
        payload = np.zeros(int(length[0]), dtype=np.uint8)
    payload = np.asarray(broadcast(payload, root_rank,
                                   name=f"{name}.data",
                                   process_set=process_set))
    return pickle.loads(payload.tobytes())


def allgather_object(obj: Any, name: str = "allgather_object",
                     process_set: ProcessSet = global_process_set) -> List:
    """Gather arbitrary picklable objects from all ranks (reference:
    torch/functions.py:230-262)."""
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    sizes = np.asarray(allgather(
        np.array([payload.size], dtype=np.int64),
        name=f"{name}.len", process_set=process_set))
    gathered = np.asarray(allgather(payload, name=f"{name}.data",
                                    process_set=process_set))
    out, off = [], 0
    for s in sizes.reshape(-1):
        out.append(pickle.loads(gathered[off:off + int(s)].tobytes()))
        off += int(s)
    return out


def metric_average(value, name: str,
                   process_set: ProcessSet = global_process_set) -> float:
    """Average a scalar metric across ranks (reference: the
    MetricAverageCallback pattern, _keras/callbacks.py)."""
    arr = np.asarray(value, dtype=np.float64)
    return float(np.asarray(allreduce(arr, op=Average, name=name,
                                      process_set=process_set)))

from . import elastic  # noqa: E402  (elastic needs the names above)
__all__.append("elastic")

from .sync_batch_norm import SyncBatchNorm, sync_batch_stats  # noqa: E402
__all__ += ["SyncBatchNorm", "sync_batch_stats"]
