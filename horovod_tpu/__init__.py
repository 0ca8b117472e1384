"""horovod_tpu: a TPU-native distributed deep-learning training framework
with the capabilities of Horovod.

Usage mirrors Horovod (``import horovod_tpu as hvd``): ``hvd.init()``,
``hvd.rank()/size()``, ``hvd.allreduce(...)``, framework bindings under
``horovod_tpu.jax`` / ``horovod_tpu.torch`` / ``horovod_tpu.tensorflow``
/ ``horovod_tpu.keras``, the ``horovodrun``-style launcher in
``horovod_tpu.runner``, and elastic training in ``horovod_tpu.elastic``.

The data plane lowers to XLA collectives over the TPU ICI mesh; the
control plane (negotiation, fusion, caching, elasticity) runs on the
TPU-VM hosts.  See ``horovod_tpu.parallel`` for the in-graph mesh API
(dp/fsdp/tp/sp/ep axes, ring attention, Ulysses) that goes beyond the
reference's data-parallel-only feature set.
"""

import time as _time

_import_start = _time.time()

from .version import __version__

from .common.basics import (Adasum, Average, Max, Min, Product, Sum,
                            ProcessSet, add_process_set,
                            cluster_metrics_snapshot,
                            cross_rank, cross_size, global_process_set,
                            gloo_built, gloo_enabled, init, is_homogeneous,
                            is_initialized, local_chips, local_rank,
                            local_size, metrics_snapshot, mpi_built,
                            mpi_enabled,
                            mpi_threads_supported, nccl_built, num_chips,
                            rank, remove_process_set, shutdown, size,
                            slo_status, spans,
                            start_timeline, status, stop_timeline,
                            cuda_built,
                            rocm_built, ccl_built, tune_status,
                            xla_built, xla_enabled)

from .common.exceptions import (HorovodInternalError,
                                HostsUpdatedInterrupt)

from .ops import (Handle, allgather, allgather_async, allreduce,
                  allreduce_async, alltoall, alltoall_async, barrier,
                  broadcast, broadcast_async, grouped_allreduce,
                  grouped_allreduce_async, join, poll, reducescatter,
                  reducescatter_async, synchronize)

from . import parallel
from . import serve
from . import sparse

__all__ = [
    "__version__",
    # basics
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "num_chips", "local_chips",
    "is_homogeneous", "mpi_threads_supported", "mpi_built", "mpi_enabled",
    "gloo_built", "gloo_enabled", "nccl_built", "cuda_built", "rocm_built",
    "ccl_built", "xla_built", "xla_enabled",
    "start_timeline", "stop_timeline",
    "metrics_snapshot", "cluster_metrics_snapshot", "tune_status",
    "status", "slo_status", "spans",
    "ProcessSet", "global_process_set", "add_process_set",
    "remove_process_set",
    # ops & op constants
    "Average", "Sum", "Adasum", "Min", "Max", "Product",
    "Handle", "allreduce", "allreduce_async", "grouped_allreduce",
    "grouped_allreduce_async", "allgather", "allgather_async",
    "broadcast", "broadcast_async", "alltoall", "alltoall_async",
    "reducescatter", "reducescatter_async", "join", "barrier", "poll",
    "synchronize",
    # exceptions
    "HorovodInternalError", "HostsUpdatedInterrupt",
    # subpackages
    "parallel", "serve", "sparse",
]

from .common import timeline as _timeline

# hvd/import: the first to the last line of this file (the import of jax
# among them), a cold span like hvd/init.  A module that this file does
# not import (horovod_tpu.training, horovod_tpu.models) records its own.
_timeline.record("import", _import_start, _time.time(), module=__name__)
del _time, _timeline, _import_start
