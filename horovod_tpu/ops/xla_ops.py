"""Multi-process XLA data plane: fused collectives over the global mesh.

The TPU analog of the reference's NCCL ops (reference:
ops/nccl_operations.{h,cc} — device-resident fused-buffer collectives):
every process places its tensor as one shard of a global array over a
"world" mesh (one representative device per process), and the fused
batch executes as a single jit-compiled program of XLA collectives —
riding ICI between chips of one slice and DCN across slices.

Compiled-executable caching is jax.jit's: a fused batch with the same
(op, shapes, dtypes) signature reuses its executable, which is exactly
the response-cache → executable-cache mapping described in SURVEY §7.

Process sets execute on sub-meshes containing only the member ranks'
devices (the analog of subset communicators, reference
controller.h:112-117); non-member processes skip the program entirely.

On CPU test rigs the same code runs over the gloo cross-process
collective implementation (see basics._maybe_init_jax_distributed).
"""

import logging
from functools import lru_cache
from typing import Any, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import env as env_mod
from ..common import metrics
from .backend import Backend, even_row_counts

logger = logging.getLogger("horovod_tpu.xla_ops")


def _is_unsigned(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.unsignedinteger)


def _reduce(x, reduce_op: str, axis: str):
    """Dtype-correct reduction.  Min/Max for unsigned ints can't use the
    negate-pmax trick (wraparound), so they gather+reduce instead."""
    if reduce_op == "Sum":
        return jax.lax.psum(x, axis)
    if reduce_op == "Average":
        return jax.lax.pmean(x, axis)
    if reduce_op == "Max":
        if _is_unsigned(x):
            return jnp.max(jax.lax.all_gather(x, axis), axis=0)
        return jax.lax.pmax(x, axis)
    if reduce_op == "Min":
        if _is_unsigned(x):
            return jnp.min(jax.lax.all_gather(x, axis), axis=0)
        return -jax.lax.pmax(-x, axis)
    if reduce_op == "Product":
        return jnp.prod(jax.lax.all_gather(x, axis), axis=0)
    raise ValueError(f"unknown reduce op {reduce_op!r}")


class XlaMeshBackend(Backend):
    name = "xla"

    def __init__(self, state):
        self.state = state
        self.size = state.rank_info.size
        self.rank = state.rank_info.rank
        self.stats = {"hierarchical_allreduces": 0, "flat_allreduces": 0}
        devices = jax.devices()
        by_proc = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        if len(by_proc) != self.size:
            raise RuntimeError(
                f"jax sees {len(by_proc)} processes but HOROVOD_SIZE="
                f"{self.size}; was jax.distributed initialized?")
        # Every rank's devices, in HOROVOD_RANK order.
        by_rank = [sorted(by_proc[p], key=lambda d: d.id)
                   for p in self._process_index_by_rank()]
        # One representative device per process carries the flat eager
        # data plane; in-graph training uses the full device set.
        self._reps = [v[0] for v in by_rank]
        self.mesh = Mesh(np.array(self._reps), ("world",))
        self.rep_device = self._reps[self.rank]
        self._init_hierarchy(by_rank, state.rank_info)

    def _process_index_by_rank(self) -> List[int]:
        """The jax process index of every rank.  The launcher hands
        jax.distributed each rank as its process id, but a backend may
        number its processes another way: the TPU runtime goes by where
        a process's chip sits on the host (measured on two v5e 2x2
        machines: ranks 0..3 came up as processes 3, 2, 0, 1 on one and
        1, 3, 2, 0 on the other).  So the order is
        exchanged through the coordination service, never assumed; a
        world mesh in process order would hand every rank-indexed
        collective (allgather, broadcast, alltoall) another rank's
        slot."""
        from jax._src import distributed
        client = distributed.global_state.client
        if client is None:
            raise RuntimeError("jax.distributed is not initialized")
        key = "hvd_xla/process_index/%d"
        client.key_value_set(key % self.rank, str(jax.process_index()),
                             allow_overwrite=True)
        timeout_ms = int(env_mod.start_timeout() * 1000)
        return [int(client.blocking_key_value_get(key % r, timeout_ms))
                for r in range(self.size)]

    def _init_hierarchy(self, by_rank, ri):
        """Build the 2-level (cross, local) mesh behind
        HOROVOD_HIERARCHICAL_ALLREDUCE (reference:
        NCCLHierarchicalAllreduce, ops/nccl_operations.cc:188-360 —
        intra-node reduce-scatter, cross-node allreduce, intra-node
        allgather; here local=ICI, cross=DCN).

        Two topologies map onto the local axis:
          * ``device``: each process drives several chips (one process
            per TPU-VM host) — the fused buffer shards across the local
            chips, so the cross-host leg runs per-chip in parallel and
            no chip idles (the eager path uses ALL local devices);
          * ``proc``: several ranks share a host (CPU rigs, one chip
            per process) — classic Horovod local ranks.
        The knob is consulted per call, so the autotuner can flip it at
        runtime (parameter sync, reference controller.cc:39-53).
        """
        self._hier = None
        self._hier_kind = None
        self.local_devices = by_rank[self.rank]
        ndev = min(len(v) for v in by_rank)
        if ndev > 1:
            grid = np.array([v[:ndev] for v in by_rank])
            self._hier = Mesh(grid, ("cross", "local"))
            self._hier_kind = "device"
            self._hier_nlocal = ndev
        elif (ri.local_size > 1 and
                ri.size == ri.cross_size * ri.local_size and
                ri.rank == ri.cross_rank * ri.local_size + ri.local_rank):
            grid = np.array(self._reps).reshape(
                ri.cross_size, ri.local_size)
            self._hier = Mesh(grid, ("cross", "local"))
            self._hier_kind = "proc"
            self._hier_nlocal = ri.local_size

    def hierarchical_active(self, ps_ranks=()) -> bool:
        knob = self.state.knobs.hierarchical_allreduce
        if knob is None:
            # Auto default: the ``device`` topology means this process
            # drives several chips — the flat world-mesh op would use
            # one chip per process and idle the rest, so the sharded
            # hierarchical layout is the default there.
            knob = self._hier_kind == "device"
        return bool(knob) and self._hier is not None and not ps_ranks

    # ------------------------------------------------------------------
    # process-set sub-meshes
    # ------------------------------------------------------------------
    @lru_cache(maxsize=64)
    def _submesh(self, ps_ranks: Tuple[int, ...]) -> Mesh:
        if not ps_ranks:
            return self.mesh
        return Mesh(np.array([self._reps[r] for r in ps_ranks]),
                    ("world",))

    def _group(self, ps_ranks: Tuple[int, ...]):
        """(mesh, group_size, my_index) for a process set."""
        if not ps_ranks:
            return self.mesh, self.size, self.rank
        return (self._submesh(tuple(ps_ranks)), len(ps_ranks),
                list(ps_ranks).index(self.rank))

    def world_size(self, ps_ranks=()) -> int:
        return len(ps_ranks) if ps_ranks else self.size

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _to_global(self, x, mesh: Mesh, group_size: int):
        """Place this process's tensor as its shard of the
        (group_size, ...) global array."""
        was_jax = isinstance(x, jax.Array)
        arr = np.asarray(x) if not was_jax else x
        local = jax.device_put(jnp.asarray(arr)[None], self.rep_device)
        g = jax.make_array_from_single_device_arrays(
            (group_size,) + tuple(arr.shape),
            NamedSharding(mesh, P("world")), [local])
        return g, was_jax

    @staticmethod
    def _from_replicated(g: jax.Array, was_jax: bool):
        local = g.addressable_data(0)
        return local if was_jax else np.asarray(local)

    # ------------------------------------------------------------------
    # allreduce
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=512)
    def _allreduce_fn(mesh, n: int, reduce_op: str, prescale: float,
                      postscale: float):
        def hvd_allreduce(*xs):
            out = []
            for x in xs:
                x = x[0]  # this process's shard (1, ...) -> (...)
                if prescale != 1.0:
                    x = (x * jnp.asarray(prescale, x.dtype)
                         if jnp.issubdtype(x.dtype, jnp.inexact)
                         else (x * prescale).astype(x.dtype))
                y = _reduce(x, reduce_op, "world")
                if postscale != 1.0:
                    y = (y * jnp.asarray(postscale, y.dtype)
                         if jnp.issubdtype(y.dtype, jnp.inexact)
                         else (y * postscale).astype(y.dtype))
                out.append(y)
            return tuple(out)

        return jax.jit(jax.shard_map(
            hvd_allreduce, mesh=mesh,
            in_specs=tuple(P("world") for _ in range(n)),
            out_specs=tuple(P() for _ in range(n)), check_vma=False))

    @metrics.timed_collective("xla", "ALLREDUCE", metrics.list_nbytes)
    def allreduce(self, arrays, reduce_op, prescale, postscale,
                  ps_ranks=()):
        if self.hierarchical_active(ps_ranks) and \
                reduce_op in ("Sum", "Average"):
            self.stats["hierarchical_allreduces"] += 1
            return self._hierarchical_allreduce(
                arrays, reduce_op, prescale, postscale)
        self.stats["flat_allreduces"] += 1
        mesh, gsize, _ = self._group(tuple(ps_ranks))
        globals_, meta = [], []
        for x in arrays:
            g, was_jax = self._to_global(x, mesh, gsize)
            globals_.append(g)
            meta.append(was_jax)
        fn = self._allreduce_fn(mesh, len(globals_), reduce_op,
                                float(prescale), float(postscale))
        outs = fn(*globals_)
        return [self._from_replicated(o, wj)
                for o, wj in zip(outs, meta)]

    # ------------------------------------------------------------------
    # hierarchical allreduce: local reduce-scatter → cross allreduce →
    # local allgather (reference ops/nccl_operations.cc:188-360)
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=256)
    def _hier_proc_fn(mesh, shapes, reduce_op: str, prescale: float,
                      postscale: float, divisor: int):
        """Each rank holds a full copy: reduce-scatter over the local
        (intra-host) axis, allreduce the shards over the cross axis,
        allgather back over local.  Input/output: flat padded buffers."""
        def hvd_hier_allreduce_proc(*xs):
            out = []
            for x in xs:
                x = x[0, 0]
                if prescale != 1.0:
                    x = x * jnp.asarray(prescale, x.dtype)
                y = jax.lax.psum_scatter(x, "local",
                                         scatter_dimension=0, tiled=True)
                y = jax.lax.psum(y, "cross")
                y = jax.lax.all_gather(y, "local", axis=0, tiled=True)
                scale = postscale / divisor if reduce_op == "Average" \
                    else postscale
                if scale != 1.0:
                    y = y * jnp.asarray(scale, y.dtype)
                out.append(y)
            return tuple(out)
        n = len(shapes)
        return jax.jit(jax.shard_map(
            hvd_hier_allreduce_proc, mesh=mesh,
            in_specs=tuple(P("cross", "local") for _ in range(n)),
            out_specs=tuple(P() for _ in range(n)), check_vma=False))

    @staticmethod
    @lru_cache(maxsize=256)
    def _hier_dev_fn(mesh, shapes, reduce_op: str, prescale: float,
                     postscale: float, divisor: int):
        """Each process's buffer is already scattered over its local
        chips: allreduce each shard over the cross axis (parallel
        per-chip streams), allgather over local to rebuild the full
        tensor.  Input: (nproc, nlocal, chunk) globals."""
        def hvd_hier_allreduce_dev(*xs):
            out = []
            for x in xs:
                x = x[0, 0]
                if prescale != 1.0:
                    x = x * jnp.asarray(prescale, x.dtype)
                y = jax.lax.psum(x, "cross")
                y = jax.lax.all_gather(y, "local", axis=0, tiled=True)
                scale = postscale / divisor if reduce_op == "Average" \
                    else postscale
                if scale != 1.0:
                    y = y * jnp.asarray(scale, y.dtype)
                out.append(y)
            return tuple(out)
        n = len(shapes)
        return jax.jit(jax.shard_map(
            hvd_hier_allreduce_dev, mesh=mesh,
            in_specs=tuple(P("cross", "local") for _ in range(n)),
            out_specs=tuple(P() for _ in range(n)), check_vma=False))

    def _hierarchical_allreduce(self, arrays, reduce_op, prescale,
                                postscale):
        mesh = self._hier
        nlocal = self._hier_nlocal
        ncross = self.size if self._hier_kind == "device" else \
            self.size // nlocal
        divisor = self.size
        flats, meta = [], []
        for x in arrays:
            was_jax = isinstance(x, jax.Array)
            arr = jnp.asarray(x) if was_jax else jnp.asarray(np.asarray(x))
            shape = arr.shape
            flat = arr.reshape(-1)
            n = flat.shape[0]
            pad = (-n) % nlocal
            if pad:
                flat = jnp.pad(flat, (0, pad))
            flats.append(flat)
            meta.append((was_jax, shape, n))
        if self._hier_kind == "device":
            globals_ = []
            for flat in flats:
                chunk = flat.shape[0] // nlocal
                pieces = flat.reshape(nlocal, chunk)
                shards = [jax.device_put(pieces[i][None, None],
                                         self.local_devices[i])
                          for i in range(nlocal)]
                globals_.append(jax.make_array_from_single_device_arrays(
                    (ncross, nlocal, chunk),
                    NamedSharding(mesh, P("cross", "local")), shards))
            fn = self._hier_dev_fn(
                mesh, tuple(f.shape for f in flats), reduce_op,
                float(prescale), float(postscale), divisor)
        else:
            globals_ = []
            for flat in flats:
                local = jax.device_put(flat[None, None], self.rep_device)
                globals_.append(jax.make_array_from_single_device_arrays(
                    (ncross, nlocal) + tuple(flat.shape),
                    NamedSharding(mesh, P("cross", "local")), [local]))
            fn = self._hier_proc_fn(
                mesh, tuple(f.shape for f in flats), reduce_op,
                float(prescale), float(postscale), divisor)
        outs = fn(*globals_)
        results = []
        for o, (was_jax, shape, n) in zip(outs, meta):
            local = o.addressable_data(0)
            r = local[:n].reshape(shape)
            results.append(r if was_jax else np.asarray(r))
        return results

    @metrics.timed_collective("xla", "ADASUM", metrics.list_nbytes)
    def adasum_allreduce(self, arrays, prescale, postscale, ps_ranks=()):
        from .adasum import adasum_allreduce_global
        mesh, gsize, _ = self._group(tuple(ps_ranks))
        return adasum_allreduce_global(
            mesh, self.rep_device, gsize, arrays, prescale, postscale)

    # ------------------------------------------------------------------
    # allgather (per-tensor per-rank sizes via padding)
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=256)
    def _gather_fn(mesh, tsizes_per_tensor: Tuple[Tuple[int, ...], ...]):
        """Gather + per-rank unpad + concat, all inside one compiled
        program (device-resident: no host round-trip; reference analog
        is the fused allgather displacement math in
        ops/collective_operations.cc).  ``tsizes_per_tensor`` is static
        per executable — a different row layout compiles a new program,
        same as any shape change."""
        def hvd_allgather(*xs):
            out = []
            for x, tsizes in zip(xs, tsizes_per_tensor):
                full = jax.lax.all_gather(x[0], "world", axis=0,
                                          tiled=False)
                pieces = [full[r, :tsizes[r]] for r in range(len(tsizes))]
                out.append(jnp.concatenate(pieces, axis=0))
            return tuple(out)
        n = len(tsizes_per_tensor)
        return jax.jit(jax.shard_map(
            hvd_allgather, mesh=mesh,
            in_specs=tuple(P("world") for _ in range(n)),
            out_specs=tuple(P() for _ in range(n)), check_vma=False))

    @metrics.timed_collective("xla", "ALLGATHER", metrics.list_nbytes)
    def allgather(self, arrays, sizes, ps_ranks=()):
        """``sizes`` holds ``group_size`` entries per tensor, in tensor
        order (fused responses concatenate them)."""
        mesh, gsize, _ = self._group(tuple(ps_ranks))
        per_tensor_sizes = [tuple(sizes[i * gsize:(i + 1) * gsize])
                            for i in range(len(arrays))]
        globals_, meta = [], []
        for x, tsizes in zip(arrays, per_tensor_sizes):
            was_jax = isinstance(x, jax.Array)
            arr = jnp.asarray(x) if was_jax else \
                jnp.asarray(np.asarray(x))
            if arr.ndim == 0:
                arr = arr[None]
            rows = arr.shape[0]
            max_rows = max(tsizes) if tsizes else rows
            if rows < max_rows:
                pad_widths = [(0, max_rows - rows)] + \
                    [(0, 0)] * (arr.ndim - 1)
                arr = jnp.pad(arr, pad_widths)
            g, _ = self._to_global(arr, mesh, gsize)
            globals_.append(g)
            meta.append(was_jax)
        fn = self._gather_fn(mesh, tuple(per_tensor_sizes))
        outs = fn(*globals_)
        return [self._from_replicated(o, wj)
                for o, wj in zip(outs, meta)]

    # ------------------------------------------------------------------
    # broadcast
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=256)
    def _bcast_fn(mesh, n: int, root: int):
        def hvd_broadcast(*xs):
            out = []
            for x in xs:
                x = x[0]
                idx = jax.lax.axis_index("world")
                masked = jnp.where(idx == root, x, jnp.zeros_like(x))
                out.append(jax.lax.psum(masked, "world"))
            return tuple(out)
        return jax.jit(jax.shard_map(
            hvd_broadcast, mesh=mesh,
            in_specs=tuple(P("world") for _ in range(n)),
            out_specs=tuple(P() for _ in range(n)), check_vma=False))

    @metrics.timed_collective("xla", "BROADCAST", metrics.list_nbytes)
    def broadcast(self, arrays, root_rank, ps_ranks=()):
        mesh, gsize, _ = self._group(tuple(ps_ranks))
        root = list(ps_ranks).index(root_rank) if ps_ranks else root_rank
        globals_, meta = [], []
        for x in arrays:
            g, was_jax = self._to_global(x, mesh, gsize)
            globals_.append(g)
            meta.append(was_jax)
        fn = self._bcast_fn(mesh, len(globals_), int(root))
        outs = fn(*globals_)
        return [self._from_replicated(o, wj)
                for o, wj in zip(outs, meta)]

    # ------------------------------------------------------------------
    # alltoall (uneven splits via pad-to-max exchange)
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=256)
    def _a2a_fn(mesh):
        def hvd_alltoall(x):
            y = jax.lax.all_to_all(x[0], "world", split_axis=0,
                                   concat_axis=0, tiled=True)
            return y[None]
        return jax.jit(jax.shard_map(
            hvd_alltoall, mesh=mesh, in_specs=P("world"),
            out_specs=P("world"), check_vma=False))

    @staticmethod
    @lru_cache(maxsize=256)
    def _a2a_pack_fn(send_splits: Tuple[int, ...], maxchunk: int,
                     shape: Tuple[int, ...], dtype: str):
        """Device-side scatter of the concatenated send buffer into the
        padded (gsize, maxchunk, ...) exchange layout.  Runs OUTSIDE the
        collective program: send splits differ per rank, and every
        rank's shard_map program must stay identical (SPMD)."""
        gsize = len(send_splits)

        @jax.jit
        def hvd_alltoall_pack(x):
            chunks = jnp.zeros((gsize, maxchunk) + x.shape[1:],
                               dtype=x.dtype)
            off = 0
            for r in range(gsize):
                c = send_splits[r]
                if c:
                    chunks = chunks.at[r, :c].set(
                        jax.lax.slice_in_dim(x, off, off + c, axis=0))
                off += c
            return chunks
        return hvd_alltoall_pack

    @staticmethod
    @lru_cache(maxsize=256)
    def _a2a_unpack_fn(recv_splits: Tuple[int, ...],
                       shape: Tuple[int, ...], dtype: str):
        gsize = len(recv_splits)

        @jax.jit
        def hvd_alltoall_unpack(y):
            pieces = [jax.lax.slice_in_dim(y[r], 0, recv_splits[r],
                                           axis=0)
                      for r in range(gsize) if recv_splits[r]]
            if not pieces:
                return y[0, :0]
            return jnp.concatenate(pieces, axis=0)
        return hvd_alltoall_unpack

    @metrics.timed_collective("xla", "ALLTOALL", metrics.one_nbytes)
    def alltoall(self, array, splits, ps_ranks=(), split_matrix=None):
        mesh, gsize, my_idx = self._group(tuple(ps_ranks))
        was_jax = isinstance(array, jax.Array)
        arr = jnp.asarray(array) if was_jax else \
            jnp.asarray(np.asarray(array))
        if splits is None:
            splits = np.array(even_row_counts(arr.shape[0], gsize),
                              dtype=np.int64)
        splits = np.asarray(splits, dtype=np.int64)
        if split_matrix is not None and len(split_matrix) == gsize * gsize:
            # Coordinator piggybacked every rank's send splits on the
            # response (reference AlltoallGetRecvSplits,
            # mpi_controller.cc:212-223) — no split-exchange collective.
            split_mat = np.asarray(split_matrix,
                                   dtype=np.int64).reshape(gsize, gsize)
        else:
            # Direct (runtime-less) call: exchange the split matrix on
            # the data plane (small; the recv split vector is part of
            # the public API so it lives on the host anyway).
            split_mat = np.asarray(self.allgather(
                [splits], sizes=[gsize] * gsize,
                ps_ranks=ps_ranks)[0]).reshape(gsize, gsize)
        recv_splits = split_mat[:, my_idx].copy()
        maxchunk = int(split_mat.max()) if split_mat.size else 0
        pack = self._a2a_pack_fn(tuple(int(s) for s in splits), maxchunk,
                                 tuple(arr.shape), str(arr.dtype))
        chunks = pack(arr)
        g, _ = self._to_global(chunks, mesh, gsize)
        out = self._a2a_fn(mesh)(g)
        mine = out.addressable_data(0)[0]  # (group, maxchunk, ...)
        unpack = self._a2a_unpack_fn(
            tuple(int(s) for s in recv_splits), tuple(mine.shape),
            str(mine.dtype))
        result = unpack(mine)
        if not was_jax:
            result = np.asarray(result)
        return result, recv_splits

    # ------------------------------------------------------------------
    # reducescatter — device-side psum_scatter (1/size the bandwidth of
    # allreduce-then-slice; this is the FSDP building block)
    # ------------------------------------------------------------------
    @staticmethod
    @lru_cache(maxsize=256)
    def _rs_fn(mesh, n: int, reduce_op: str):
        def hvd_reducescatter(*xs):
            out = []
            for x in xs:
                x = x[0]  # (group*chunk, ...) contribution
                if reduce_op == "Average":
                    y = jax.lax.psum_scatter(
                        x, "world", scatter_dimension=0, tiled=True)
                    y = y / jax.lax.psum(1, "world")
                else:
                    y = jax.lax.psum_scatter(
                        x, "world", scatter_dimension=0, tiled=True)
                out.append(y[None])
            return tuple(out)
        return jax.jit(jax.shard_map(
            hvd_reducescatter, mesh=mesh,
            in_specs=tuple(P("world") for _ in range(n)),
            out_specs=tuple(P("world") for _ in range(n)),
            check_vma=False))

    @staticmethod
    @lru_cache(maxsize=256)
    def _rs_pack_fn(counts: Tuple[int, ...], chunk: int,
                    shape: Tuple[int, ...], dtype: str):
        """Device-side boundary-correct layout: slot r of the padded
        buffer holds exactly rank r's target rows (zero-padded), so the
        even psum_scatter split lands each rank on its uneven share."""
        gsize = len(counts)
        starts = [0]
        for c in counts[:-1]:
            starts.append(starts[-1] + c)

        @jax.jit
        def hvd_reducescatter_pack(arr):
            padded = jnp.zeros((gsize, chunk) + arr.shape[1:], arr.dtype)
            for r in range(gsize):
                if counts[r]:
                    padded = padded.at[r, :counts[r]].set(
                        jax.lax.slice_in_dim(arr, starts[r],
                                             starts[r] + counts[r],
                                             axis=0))
            return padded.reshape((gsize * chunk,) + arr.shape[1:])
        return hvd_reducescatter_pack

    @metrics.timed_collective("xla", "REDUCESCATTER", metrics.list_nbytes)
    def reducescatter(self, arrays, reduce_op, ps_ranks=()):
        """Rank r receives its dim-0 shard of the sum; first ranks absorb
        the remainder (uneven-split convention matching allgather)."""
        mesh, gsize, my_idx = self._group(tuple(ps_ranks))
        prepped, meta = [], []
        for x in arrays:
            was_jax = isinstance(x, jax.Array)
            arr = jnp.asarray(x) if was_jax else \
                jnp.asarray(np.asarray(x))
            rows = arr.shape[0]
            counts = tuple(even_row_counts(rows, gsize))
            chunk = max(counts) if counts else 0
            pack = self._rs_pack_fn(counts, chunk, tuple(arr.shape),
                                    str(arr.dtype))
            prepped.append(pack(arr))
            meta.append((was_jax, counts[my_idx]))
        globals_ = [self._to_global(p, mesh, gsize)[0] for p in prepped]
        fn = self._rs_fn(mesh, len(globals_), reduce_op)
        outs = fn(*globals_)
        results = []
        for o, (was_jax, my_count) in zip(outs, meta):
            mine = o.addressable_data(0)[0]
            mine = jax.lax.slice_in_dim(mine, 0, my_count, axis=0)
            results.append(mine if was_jax else np.asarray(mine))
        return results

    def barrier(self, ps_ranks=()):
        self.allreduce([np.zeros(1, np.float32)], "Sum", 1.0, 1.0,
                       ps_ranks)
        return None
