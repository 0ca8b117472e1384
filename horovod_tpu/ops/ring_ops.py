"""CPU eager data plane over the native TCP ring collectives.

The analog of the reference's Gloo CPU backend (reference:
ops/gloo_operations.{h,cc} ring algorithms over the full-mesh TCP
contexts of gloo/gloo_context.cc).  On TPU the data plane is compiled
XLA collectives over ICI (:mod:`.xla_ops`); on CPU rigs, dispatching a
multi-controller XLA program costs milliseconds per call, while the
native ring over persistent sockets costs microseconds — so this
backend owns the host-tensor hot path (allreduce/allgather/broadcast/
alltoall/reducescatter/barrier) and delegates the rest (Adasum,
complex dtypes) to the XLA backend.

Selection (reference knob HOROVOD_CPU_OPERATIONS, common.h:84-89):
``HOROVOD_CPU_OPERATIONS=RING`` (default on CPU) or ``XLA``.
"""

import ctypes
import logging
import os
import threading
import time
from typing import List

import numpy as np

from ..common import env as env_mod
from ..common import failpoints as _fp
from ..common import metrics
from .backend import Backend, even_row_counts

logger = logging.getLogger("horovod_tpu.ring")

_DTYPES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
}
# Upcast table for dtypes the C kernels don't reduce natively.
_UPCAST = {
    np.dtype(np.float16): np.float32,
    np.dtype(np.int8): np.int32,
    np.dtype(np.uint8): np.int32,
    np.dtype(np.int16): np.int32,
    np.dtype(np.uint16): np.int32,
    np.dtype(np.uint32): np.int64,
    # bool reduces as int32; astype(bool) on the way out restores
    # logical semantics (Min=AND, Max=OR, Sum=count-nonzero-saturated).
    np.dtype(np.bool_): np.int32,
}
try:
    import ml_dtypes
    _UPCAST[np.dtype(ml_dtypes.bfloat16)] = np.float32
except ImportError:
    pass

_OPS = {"Sum": 0, "Average": 0, "Product": 1, "Min": 2, "Max": 3}

# XLA's CPU client zero-copies host buffers only at this alignment;
# anything less costs a copy (plus a fence under a distributed client).
_XLA_ALIGN = 128


def _aligned_empty(shape, dtype, align=_XLA_ALIGN) -> np.ndarray:
    """Fresh C-contiguous array whose data pointer is ``align``-ed, so
    jax can adopt it zero-copy (see _rewrap)."""
    dt = np.dtype(dtype)
    if isinstance(shape, (int, np.integer)):
        shape = (int(shape),)
    n = int(np.prod(shape, initial=1))
    raw = np.empty(n * dt.itemsize + align, np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + n * dt.itemsize].view(dt).reshape(shape)


def _bind(lib):
    lib.hvd_ring_create.restype = ctypes.c_void_p
    lib.hvd_ring_create.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.hvd_ring_listen.restype = ctypes.c_int
    lib.hvd_ring_listen.argtypes = [ctypes.c_void_p]
    lib.hvd_ring_connect.restype = ctypes.c_int
    lib.hvd_ring_connect.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.hvd_ring_allreduce.restype = ctypes.c_int
    lib.hvd_ring_allreduce.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.c_int]
    lib.hvd_ring_allgather.restype = ctypes.c_int
    lib.hvd_ring_allgather.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_ring_alltoall.restype = ctypes.c_int
    lib.hvd_ring_alltoall.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_ring_reducescatter.restype = ctypes.c_int
    lib.hvd_ring_reducescatter.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_ring_broadcast.restype = ctypes.c_int
    lib.hvd_ring_broadcast.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_ring_barrier.restype = ctypes.c_int
    lib.hvd_ring_barrier.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_ring_shm_setup.restype = ctypes.c_int
    lib.hvd_ring_shm_setup.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_int)]
    lib.hvd_ring_shm_enable.argtypes = [ctypes.c_void_p]
    lib.hvd_ring_shm_unlink_name.argtypes = [ctypes.c_void_p]
    lib.hvd_ring_shm_active.restype = ctypes.c_int
    lib.hvd_ring_shm_active.argtypes = [ctypes.c_void_p]
    lib.hvd_ring_destroy.argtypes = [ctypes.c_void_p]


def _kv_client():
    from jax._src import distributed as _dist
    client = _dist.global_state.client
    if client is None:
        raise RuntimeError("jax.distributed is not initialized")
    return client


class RingBackend(Backend):
    name = "ring"

    def __init__(self, state, fallback: Backend):
        from ..native import load

        self.state = state
        self.fallback = fallback
        self.size = state.rank_info.size
        self.rank = state.rank_info.rank
        # Shared stats dict: ring counters live next to the fallback's
        # (hierarchical/flat) counters so observers see one view.
        self.stats = getattr(fallback, "stats", {})
        self.stats.setdefault("ring_allreduces", 0)
        # Persistent per-dtype staging buffers (reference:
        # fusion_buffer_manager.{h,cc}) — see _fused().  Normally only
        # the background runtime thread dispatches collectives, but
        # allreduce/reducescatter are public; the lock makes a direct
        # concurrent call serialize instead of corrupting the shared
        # staging buffer.
        self._fusion_bufs = {}
        self._fusion_lock = threading.Lock()
        self._lib = None
        self._comm = None
        self._keys = []
        lib = load()
        # The backend choice must be COLLECTIVE: one rank on the ring
        # while another silently falls back to XLA would hang at the
        # first op. Every rank walks the SAME two rounds regardless of
        # local failures: (1) publish its ring address or a FAIL
        # marker, read everyone's; (2) publish connect ok/failed, read
        # everyone's. Unanimity decides; even a failing rank completes
        # both rounds before tearing down, so peers observe its markers
        # promptly (no blocking-get timeout).
        #
        # The namespace is INCARNATION-SCOPED so one incarnation's keys
        # can never be read by another: elastic epochs already get
        # fresh controller endpoints per replan (distinct ns), and the
        # elastic epoch is mixed in besides; static worlds mix in the
        # per-process init generation, which advances in lockstep
        # (every rank runs the same init/shutdown sequence).  This is
        # what makes teardown SAFE: a demoted rank leaves its markers
        # behind (deleting them raced a peer's blocking read into a
        # full KV timeout — a measured, intermittent ~60 s init stall),
        # and the next incarnation's reads can't be poisoned because
        # they use different keys.
        import hashlib
        try:
            from ..runner.elastic.worker import current_epoch
            epoch = current_epoch()
        except Exception:
            epoch = 0
        incarnation = (f"e{epoch}" if epoch
                       else f"g{getattr(state, 'init_generation', 0)}")
        ns = hashlib.sha1(
            (env_mod.env_str(env_mod.HOROVOD_TPU_COORDINATOR) + "|" +
             env_mod.env_str("HOROVOD_CONTROLLER_ADDR") + "|" +
             incarnation).encode()
        ).hexdigest()[:12]
        addr_key = f"hvd_ring/{ns}/addr/{{}}"
        ok_key = f"hvd_ring/{ns}/ok/{{}}"
        self._client = client = _kv_client()
        my_addr = None
        err = None
        try:
            if _fp.ENABLED:
                # Failpoint site: `ring.setup=error(rank=N)` exercises
                # the unanimous demotion protocol (see
                # tests/test_ring_backend.py, docs/fault_injection.md).
                _fp.maybe_fail("ring.setup", rank=self.rank)
            if lib is None:
                raise RuntimeError("native library unavailable")
            _bind(lib)
            self._lib = lib
            self._comm = lib.hvd_ring_create(self.rank, self.size)
            port = lib.hvd_ring_listen(self._comm)
            if port <= 0:
                raise RuntimeError("ring listen failed")
            my_addr = f"{self._my_ip()}:{port}"
        except Exception as e:
            err = e
        try:
            # Round 1: address exchange over the jax coordination-
            # service KV store (the analog of the reference's
            # rendezvous KV, gloo/gloo_context.cc:63-84).
            self._publish(addr_key.format(self.rank),
                          my_addr if err is None else "FAIL")
            addrs = [
                client.blocking_key_value_get(addr_key.format(r),
                                              60_000)
                for r in range(self.size)
            ]
            rc = -1
            if err is None and not any(a == "FAIL" for a in addrs):
                rc = lib.hvd_ring_connect(self._comm,
                                          ",".join(addrs).encode())
            # Shared-memory fast path for same-host pairs (the analog
            # of the reference's on-host shared-memory transports —
            # gloo allreduce_local / MPI vader BTL).  Host identity
            # comes from the exchanged ring IPs; setup maps the
            # per-host segment but transport only flips on after the
            # unanimity round below (a rank writing shm while its
            # neighbor reads TCP would hang the first collective).
            shm_rc, cap = None, 0  # None: disabled / failed locally
            if rc == 0 and env_mod.env_str(
                    "HOROVOD_RING_SHM", "1").strip().lower() not in (
                    "0", "false", "off", "no"):
                raw_cap = env_mod.env_str("HOROVOD_RING_SHM_CAP", "")
                try:
                    cap = int(raw_cap) if raw_cap else (1 << 20)
                except ValueError:
                    cap = 0  # bad value: lose the optimization, not
                    #          the rank's marker publish below
                if cap > 0:
                    ips = [a.rsplit(":", 1)[0] for a in addrs]
                    ids = {}
                    hostids = (ctypes.c_int * self.size)(
                        *[ids.setdefault(ip, len(ids)) for ip in ips])
                    shm_rc = lib.hvd_ring_shm_setup(
                        self._comm, f"hvdring{ns}".encode(), cap,
                        hostids)
            # Round 2: unanimous outcome.  The 60 s blocking read
            # covers the native connect/accept bounds (collectives.cc:
            # 30 s connect retry, 60 s accept poll); a local timeout
            # here must RAISE, never silently count as "0" — a rank
            # demoting alone while peers keep the ring would hang the
            # first collective.  Markers are never deleted mid-protocol
            # (see the namespace comment), so the only way to miss one
            # is a dead peer, which is fatal to the job anyway.
            # Marker values: "1:<cap>" ring + shm ok at that channel
            # capacity, "2" ring ok / shm disabled-or-failed, "0" ring
            # failed.  The ring forms on all-{1,2}; shm engages only
            # when EVERY rank published "1" with the SAME cap (env
            # asymmetry — one rank disabled, or differing
            # HOROVOD_RING_SHM_CAP and therefore differing channel
            # strides into one segment — must cost the optimization,
            # never a hang or stride corruption).
            if rc != 0:
                mine = "0"
            elif shm_rc in (0, 1):
                mine = "1:%d" % cap
            else:
                mine = "2"
            self._publish(ok_key.format(self.rank), mine)
            oks = [client.blocking_key_value_get(ok_key.format(r),
                                                 60_000)
                   for r in range(self.size)]
            if err is not None:
                raise err
            if rc != 0 or any(o != "2" and not o.startswith("1:")
                              for o in oks):
                raise RuntimeError(
                    f"ring setup incomplete (rc={rc}, oks={oks}, "
                    f"addrs={addrs}); all ranks use the XLA fallback")
            if shm_rc == 0 and all(o == "1:%d" % cap for o in oks):
                lib.hvd_ring_shm_enable(self._comm)
            if shm_rc == 0:
                # The agreement round proves every local rank has
                # mapped the segment: unlink the NAME now (mapping
                # stays alive), so even a SIGKILLed job cannot leak a
                # /dev/shm file.
                lib.hvd_ring_shm_unlink_name(self._comm)
            self.stats["ring_shm"] = bool(
                lib.hvd_ring_shm_active(self._comm))
        except Exception:
            # Demotion path: LEAVE the marker keys.  A peer may be
            # mid-blocking-read on them; deleting now races its read
            # into a full KV timeout — measured as an intermittent
            # ~60 s stall inside hvd.init() on 1-core rigs (the peer
            # then demotes anyway).  Leftovers are harmless: the
            # namespace is incarnation-scoped, so no later init can
            # read them.
            self.close(delete_keys=False)
            raise
        logger.debug("ring backend up: rank %d/%d via %s", self.rank,
                     self.size, my_addr)

    def _publish(self, key: str, value: str):
        """allow_overwrite: a crashed incarnation's stale key must not
        block a replacement worker from publishing; a peer that still
        reads a stale value fails the connect and the unanimous OK
        round demotes everyone consistently."""
        try:
            self._client.key_value_set(key, value, allow_overwrite=True)
            self._keys.append(key)
        except Exception:
            logger.debug("kv publish failed for %s", key, exc_info=True)

    @staticmethod
    def _my_ip() -> str:
        import socket
        ctrl = env_mod.env_str_opt("HOROVOD_CONTROLLER_ADDR") or \
            env_mod.env_str_opt(env_mod.HOROVOD_TPU_COORDINATOR)
        if ctrl and ":" in ctrl:
            host, _, port = ctrl.rpartition(":")
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.connect((host, int(port)))
                ip = s.getsockname()[0]
                s.close()
                return ip
            except OSError:
                pass
        return "127.0.0.1"

    def _call(self, fn, *args) -> int:
        """All C ring entry points run under the fusion lock: this
        serializes direct concurrent callers and lets close() wait out
        any in-flight collective before destroying the C comm (no
        use-after-free).  The allreduce/reducescatter paths hold the
        lock across their staging too and call the lib directly via
        _comm_checked() so a caller that was blocked on the lock while
        close() ran gets the clean closed error, not a NULL deref in
        the C ring."""
        with self._fusion_lock:
            return fn(self._comm_checked(), *args)

    def _comm_checked(self):
        """Must be called with _fusion_lock held: close() nulls _comm
        under the same lock, so a collective that acquired the lock
        after close() must re-check before handing the pointer to C
        (hvd_ring_* dereference it unchecked)."""
        if _fp.ENABLED:
            # Failpoint site on the transport funnel (every native ring
            # dispatch passes here): delay() models a slow wire, error()
            # a failed collective.  Runs under the fusion lock, so an
            # injected delay back-pressures exactly like a real stall.
            _fp.maybe_fail("ring.send", rank=self.rank)
        if self._comm is None:
            raise RuntimeError("ring backend is closed")
        return self._comm

    def close(self, delete_keys: bool = True):
        if self._comm is not None:
            # The fusion lock is held for the duration of every ring
            # call, so acquiring it serializes destroy against any
            # in-flight collective (no use-after-free on the C comm).
            with self._fusion_lock:
                self._lib.hvd_ring_destroy(self._comm)
                self._comm = None
        # Hygiene only (the namespace is incarnation-scoped, so stale
        # keys can never be read by a later init): clean up on an
        # established-ring close, where every rank necessarily finished
        # both rendezvous rounds long ago.  Skipped on the
        # init-demotion path: peers may still be blocking-reading the
        # markers (see the demotion comment in __init__).
        keys, self._keys = self._keys, []
        if not delete_keys:
            return
        for key in keys:
            try:
                self._client.key_value_delete(key)
            except Exception:
                pass

    # -- helpers ---------------------------------------------------------
    def _group_args(self, ps_ranks):
        if not ps_ranks:
            return None, 0, self.size
        arr = (ctypes.c_int * len(ps_ranks))(*ps_ranks)
        return arr, len(ps_ranks), len(ps_ranks)

    def world_size(self, ps_ranks=()) -> int:
        return len(ps_ranks) if ps_ranks else self.size

    @staticmethod
    def _scale(x: np.ndarray, factor: float) -> np.ndarray:
        if factor == 1.0:
            return x
        if np.issubdtype(x.dtype, np.inexact):
            return x * x.dtype.type(factor)
        return (x * factor).astype(x.dtype)

    @staticmethod
    def _scale_inplace(buf: np.ndarray, factor: float):
        if factor == 1.0:
            return
        if np.issubdtype(buf.dtype, np.inexact):
            buf *= buf.dtype.type(factor)
        else:
            # Integer scaling truncates, matching _scale(); the float
            # temp is the rare path (int Average / explicit factors).
            np.copyto(buf, buf * factor, casting="unsafe")

    def _fused(self, dtype: np.dtype, n: int) -> np.ndarray:
        """Persistent staging buffer per work dtype, grown geometrically
        — the CPU-ring analog of the reference's fusion buffer
        (fusion_buffer_manager.{h,cc}).  Fresh 10s-of-MB numpy arrays
        come from mmap and are returned to the OS on free, so staging
        through temporaries costs a page-fault storm per collective
        that exceeds the wire time; one hot reused buffer fixes that.
        Only the background runtime thread dispatches collectives, so
        a single buffer per dtype is safe."""
        buf = self._fusion_bufs.get(dtype.str)
        if buf is None or buf.size < n:
            cap = max(n, 2 * (buf.size if buf is not None else 0),
                      1 << 16)
            buf = np.empty(cap, dtype)
            self._fusion_bufs[dtype.str] = buf
        return buf[:n]

    # Above this, fresh-alloc page faults outweigh the saved staging
    # copy and the persistent fusion buffer wins (see _fused()).
    ONE_COPY_MAX_BYTES = 4 << 20

    def _allreduce_single_fast(self, a, reduce_op, prescale, postscale):
        """Small single-tensor fast path: ONE fresh output copy, ring
        runs in place on it — skips the fusion-buffer double copy
        (~0.2 ms at 1 MB) and the generic multi-tensor bookkeeping.
        Returns None when ineligible (caller takes the general path)."""
        was_jax = self._is_jax(a)
        src = self._np_view(a)
        dt = src.dtype
        if dt not in _DTYPES or src.nbytes > self.ONE_COPY_MAX_BYTES:
            return None
        out = _aligned_empty(src.shape, dt)  # fresh working copy
        np.copyto(out, src)
        flat = out.reshape(-1)
        self._scale_inplace(flat, prescale)
        if flat.size:
            with self._fusion_lock:      # one collective on the ring
                rc = self._lib.hvd_ring_allreduce(
                    self._comm_checked(),
                    out.ctypes.data_as(ctypes.c_void_p),
                    flat.size, _DTYPES[dt], _OPS[reduce_op], None, 0)
            if rc != 0:
                raise RuntimeError(f"ring allreduce failed (rc={rc})")
        post = postscale / self.size if reduce_op == "Average" \
            else postscale
        self._scale_inplace(flat, post)
        return [self._rewrap(out, was_jax)]

    # -- allreduce -------------------------------------------------------
    def allreduce(self, arrays, reduce_op, prescale, postscale,
                  ps_ranks=()):
        # Metrics are recorded only on native-ring completions: the
        # fallback paths delegate to the (already instrumented) XLA
        # backend, which would otherwise double-count.
        t0 = time.perf_counter()
        if len(arrays) == 1 and not ps_ranks and reduce_op in _OPS:
            fast = self._allreduce_single_fast(
                arrays[0], reduce_op, prescale, postscale)
            if fast is not None:
                self.stats["ring_allreduces"] += 1
                metrics.record_collective(
                    "ring", "ALLREDUCE", metrics.list_nbytes(arrays),
                    time.perf_counter() - t0)
                return fast
        # Dtype probing must not force a host copy of a jax input (the
        # pre-round-6 np.asarray here materialized every array twice).
        dt = np.result_type(*(getattr(a, "dtype", None) or
                              np.asarray(a).dtype for a in arrays)) \
            if arrays else np.float32
        if reduce_op not in _OPS or \
                np.issubdtype(dt, np.complexfloating):
            return self.fallback.allreduce(arrays, reduce_op, prescale,
                                           postscale, ps_ranks)
        ranks_arr, nranks, gsize = self._group_args(tuple(ps_ranks))

        was_jax = [self._is_jax(a) for a in arrays]
        nps = [self._np_view(a) for a in arrays]
        orig_dtypes = [a.dtype for a in nps]
        work_dt = np.dtype(dt)
        if work_dt in _UPCAST:
            work_dt = np.dtype(_UPCAST[work_dt])
        if work_dt not in _DTYPES:
            return self.fallback.allreduce(arrays, reduce_op, prescale,
                                           postscale, ps_ranks)
        self.stats["ring_allreduces"] += 1
        # One persistent fused buffer per call: a single copy in
        # (converting dtype on the way), the in-place ring over the
        # whole batch, scales applied in place, and one copy out per
        # tensor into its own fresh output (the reference's
        # fusion-buffer memcpy in/out, collective_operations.h:96-125).
        total = sum(a.size for a in nps)
        with self._fusion_lock:
            buf = self._fused(work_dt, total)
            off = 0
            for a in nps:
                np.copyto(buf[off:off + a.size], a.reshape(-1),
                          casting="unsafe")
                off += a.size
            self._scale_inplace(buf, prescale)
            if total:
                rc = self._lib.hvd_ring_allreduce(
                    self._comm_checked(),
                    buf.ctypes.data_as(ctypes.c_void_p),
                    total, _DTYPES[work_dt], _OPS[reduce_op],
                    ranks_arr, nranks)
                if rc != 0:
                    raise RuntimeError(
                        f"ring allreduce failed (rc={rc})")
            post = postscale
            if reduce_op == "Average":
                post = postscale / gsize
            self._scale_inplace(buf, post)
            out, off = [], 0
            for a, odt, wj in zip(nps, orig_dtypes, was_jax):
                piece = _aligned_empty(a.shape, odt)
                np.copyto(piece,
                          buf[off:off + a.size].reshape(a.shape),
                          casting="unsafe")
                off += a.size
                out.append(self._rewrap(piece, wj))
        metrics.record_collective("ring", "ALLREDUCE",
                                  metrics.list_nbytes(nps),
                                  time.perf_counter() - t0)
        return out

    @staticmethod
    def _is_jax(x) -> bool:
        import jax
        return isinstance(x, jax.Array)

    @staticmethod
    def _np_view(x) -> np.ndarray:
        """Zero-copy host view of a CPU jax array via dlpack — the
        ingestion half of the jax fast path (_rewrap is the egress
        half).  ``np.asarray`` on a jax array materializes a fresh
        host copy per call; the dlpack view aliases the XLA buffer
        instead.  The view is read-only and only ever read
        (staged into the ring's own working buffer).  Falls back to a
        copy for non-CPU buffers, bf16 (numpy's dlpack has no bf16),
        and plain numpy/list inputs."""
        if RingBackend._is_jax(x):
            try:
                return np.from_dlpack(x)
            except Exception:
                pass
        return np.asarray(x)

    @staticmethod
    def _rewrap(x: np.ndarray, was_jax: bool):
        if not was_jax:
            return x
        # Zero-copy wrap when the buffer is XLA-aligned: jax's CPU
        # client copies (and under a distributed gloo client, fences)
        # unaligned numpy inputs — measured 0.32 ms vs 0.03 ms at 1 MB
        # on the bench rig.  Outputs from _aligned_empty always take
        # the fast branch; x is a fresh per-call array we never touch
        # again, so aliasing its memory into the jax Array is safe.
        if x.ctypes.data % _XLA_ALIGN == 0 and x.flags.c_contiguous:
            try:
                import jax.dlpack
                return jax.dlpack.from_dlpack(x)
            except Exception:
                pass
        import jax.numpy as jnp
        return jnp.asarray(x)

    def adasum_allreduce(self, arrays, prescale, postscale, ps_ranks=()):
        return self.fallback.adasum_allreduce(arrays, prescale,
                                              postscale, ps_ranks)

    # -- allgather -------------------------------------------------------
    @metrics.timed_collective("ring", "ALLGATHER", metrics.list_nbytes)
    def allgather(self, arrays, sizes, ps_ranks=()):
        ranks_arr, nranks, gsize = self._group_args(tuple(ps_ranks))
        per_tensor_sizes = [sizes[i * gsize:(i + 1) * gsize]
                            for i in range(len(arrays))]
        out = []
        for x, tsizes in zip(arrays, per_tensor_sizes):
            wj = self._is_jax(x)
            a = np.ascontiguousarray(self._np_view(x))
            if a.ndim == 0:
                a = a[None]
            row_bytes = a[0:1].nbytes if a.shape[0] else \
                a.dtype.itemsize * int(np.prod(a.shape[1:], initial=1))
            counts = (ctypes.c_longlong * gsize)(
                *[int(t) * row_bytes for t in tsizes])
            total_rows = int(sum(tsizes))
            res = _aligned_empty((total_rows,) + a.shape[1:], a.dtype)
            rc = self._call(
                self._lib.hvd_ring_allgather,
                a.ctypes.data_as(ctypes.c_void_p),
                a.nbytes, res.ctypes.data_as(ctypes.c_void_p),
                counts, ranks_arr, nranks)
            if rc != 0:
                raise RuntimeError(f"ring allgather failed (rc={rc})")
            out.append(self._rewrap(res, wj))
        return out

    # -- broadcast -------------------------------------------------------
    @metrics.timed_collective("ring", "BROADCAST", metrics.list_nbytes)
    def broadcast(self, arrays, root_rank, ps_ranks=()):
        ranks_arr, nranks, _ = self._group_args(tuple(ps_ranks))
        root = list(ps_ranks).index(root_rank) if ps_ranks else root_rank
        out = []
        for x in arrays:
            wj = self._is_jax(x)
            # Broadcast mutates in place, so a copy is required — but
            # copying the dlpack VIEW into an XLA-aligned buffer costs
            # one memcpy and makes the egress rewrap zero-copy too
            # (np.array output is rarely 128-aligned).  0-d shapes are
            # preserved (ascontiguousarray would promote them to 1-d).
            src = self._np_view(x)
            a = _aligned_empty(src.shape, src.dtype)
            np.copyto(a, src)
            rc = self._call(
                self._lib.hvd_ring_broadcast,
                a.ctypes.data_as(ctypes.c_void_p),
                a.nbytes, int(root), ranks_arr, nranks)
            if rc != 0:
                raise RuntimeError(f"ring broadcast failed (rc={rc})")
            out.append(self._rewrap(a, wj))
        return out

    # -- alltoall --------------------------------------------------------
    def _my_index(self, ps_ranks) -> int:
        return ps_ranks.index(self.rank) if ps_ranks else self.rank

    @metrics.timed_collective("ring", "ALLTOALL", metrics.one_nbytes)
    def alltoall(self, array, splits, ps_ranks=(), split_matrix=None):
        """Pairwise-exchange alltoall over the native mesh, matching the
        XLA backend's semantics (splits = dim-0 row counts per
        destination; returns (output, recv_splits) — reference
        operations.cc:1099-1160, AlltoallGetRecvSplits
        mpi_controller.cc:212-223). Pure data movement, so any dtype
        goes over the wire as raw bytes.  ``split_matrix`` (flattened
        group×group, coordinator-assembled) skips the native split
        allgather when provided."""
        ps_ranks = tuple(ps_ranks)
        ranks_arr, nranks, gsize = self._group_args(ps_ranks)
        my_idx = self._my_index(ps_ranks)
        wj = self._is_jax(array)
        a = np.ascontiguousarray(self._np_view(array))
        if a.ndim == 0:
            a = a[None]
        if splits is None:
            splits = np.array(even_row_counts(a.shape[0], gsize),
                              dtype=np.int64)
        splits = np.ascontiguousarray(np.asarray(splits, np.int64))
        # Validate before anything reaches native code: a bad splits
        # vector must be a Python error, not an OOB read/write in C.
        if splits.shape != (gsize,):
            raise ValueError(
                f"splits must have one entry per group rank "
                f"({gsize}), got shape {splits.shape}")
        if (splits < 0).any() or int(splits.sum()) != a.shape[0]:
            raise ValueError(
                f"splits must be non-negative and sum to the first "
                f"dimension ({a.shape[0]}), got {splits.tolist()}")
        if split_matrix is not None and \
                len(split_matrix) == gsize * gsize:
            # Coordinator piggybacked the matrix on the response.
            recv_splits = np.asarray(split_matrix, np.int64) \
                .reshape(gsize, gsize)[:, my_idx].copy()
        else:
            # Split-matrix exchange (small): recv = column my_idx.
            mat = np.empty(gsize * gsize, np.int64)
            counts8 = (ctypes.c_longlong * gsize)(
                *([8 * gsize] * gsize))
            rc = self._call(
                self._lib.hvd_ring_allgather,
                splits.ctypes.data_as(ctypes.c_void_p),
                splits.nbytes, mat.ctypes.data_as(ctypes.c_void_p),
                counts8, ranks_arr, nranks)
            if rc != 0:
                raise RuntimeError(
                    f"ring alltoall splits failed (rc={rc})")
            recv_splits = mat.reshape(gsize, gsize)[:, my_idx].copy()

        row_bytes = a.dtype.itemsize * int(np.prod(a.shape[1:],
                                                   initial=1))
        sendcounts = (ctypes.c_longlong * gsize)(
            *[int(s) * row_bytes for s in splits])
        recvcounts = (ctypes.c_longlong * gsize)(
            *[int(s) * row_bytes for s in recv_splits])
        out = _aligned_empty((int(recv_splits.sum()),) + a.shape[1:],
                     a.dtype)
        rc = self._call(
            self._lib.hvd_ring_alltoall,
            a.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p), sendcounts, recvcounts,
            ranks_arr, nranks)
        if rc != 0:
            raise RuntimeError(f"ring alltoall failed (rc={rc})")
        self.stats["ring_alltoalls"] = \
            self.stats.get("ring_alltoalls", 0) + 1
        return self._rewrap(out, wj), recv_splits

    # -- reducescatter ---------------------------------------------------
    def reducescatter(self, arrays, reduce_op, ps_ranks=()):
        """Fused reduce-scatter: all native-eligible tensors of a work
        dtype ride ONE ring pass (k tensors would otherwise pay
        k*(p-1) latency steps), packed rank-major so the per-rank chunk
        of the fused buffer is the concatenation of every tensor's
        chunk for that rank.  Half the bandwidth of
        allreduce-then-slice; uneven dim-0 split convention matches the
        XLA backend (first ranks absorb the remainder)."""
        if reduce_op not in _OPS:
            return self.fallback.reducescatter(arrays, reduce_op,
                                               ps_ranks)
        ps_ranks = tuple(ps_ranks)
        ranks_arr, nranks, gsize = self._group_args(ps_ranks)
        my_idx = self._my_index(ps_ranks)
        out: List = [None] * len(arrays)
        groups = {}  # work dtype -> [(pos, np_array, was_jax)]
        for i, x in enumerate(arrays):
            a = self._np_view(x)
            work_dt = np.dtype(_UPCAST.get(a.dtype, a.dtype))
            if work_dt not in _DTYPES or a.ndim == 0 or \
                    np.iscomplexobj(a):
                out[i] = self.fallback.reducescatter([x], reduce_op,
                                                     ps_ranks)[0]
                continue
            groups.setdefault(work_dt.str, []).append(
                (i, a, self._is_jax(x)))
        # Timer starts AFTER the classification loop: the per-tensor
        # XLA fallbacks above already record their own wall time under
        # backend="xla" — only native-ring work belongs to this record.
        t0 = time.perf_counter()
        for dt_str, items in groups.items():
            work_dt = np.dtype(dt_str)
            rowcounts = [even_row_counts(a.shape[0], gsize)
                         for _, a, _ in items]
            rowelems = [int(np.prod(a.shape[1:], initial=1))
                        for _, a, _ in items]
            counts = [sum(rc[r] * re
                          for rc, re in zip(rowcounts, rowelems))
                      for r in range(gsize)]
            with self._fusion_lock:
                buf = self._fused(work_dt, sum(counts))  # clobbered
                off = 0
                row_off = [0] * len(items)
                for r in range(gsize):
                    for j, (_, a, _) in enumerate(items):
                        nel = rowcounts[j][r] * rowelems[j]
                        src = a[row_off[j]:
                                row_off[j] + rowcounts[j][r]]
                        np.copyto(buf[off:off + nel], src.reshape(-1),
                                  casting="unsafe")
                        row_off[j] += rowcounts[j][r]
                        off += nel
                counts_c = (ctypes.c_longlong * gsize)(*counts)
                res = np.empty(counts[my_idx], work_dt)
                rc = self._lib.hvd_ring_reducescatter(
                    self._comm_checked(),
                    buf.ctypes.data_as(ctypes.c_void_p),
                    counts_c, _DTYPES[work_dt], _OPS[reduce_op],
                    res.ctypes.data_as(ctypes.c_void_p), ranks_arr,
                    nranks)
            if rc != 0:
                raise RuntimeError(
                    f"ring reducescatter failed (rc={rc})")
            if reduce_op == "Average":
                self._scale_inplace(res, 1.0 / gsize)
            o = 0
            for j, (i, a, wj) in enumerate(items):
                myrows = rowcounts[j][my_idx]
                nel = myrows * rowelems[j]
                piece = _aligned_empty((myrows,) + a.shape[1:], a.dtype)
                np.copyto(piece, res[o:o + nel].reshape(piece.shape),
                          casting="unsafe")
                o += nel
                out[i] = self._rewrap(piece, wj)
            self.stats["ring_reducescatters"] = \
                self.stats.get("ring_reducescatters", 0) + len(items)
        if groups:
            metrics.record_collective(
                "ring", "REDUCESCATTER",
                sum(int(a.nbytes) for items in groups.values()
                    for _, a, _ in items),
                time.perf_counter() - t0)
        return out

    def barrier(self, ps_ranks=()):
        ranks_arr, nranks, _ = self._group_args(tuple(ps_ranks))
        rc = self._call(self._lib.hvd_ring_barrier, ranks_arr,
                        nranks)
        if rc != 0:
            raise RuntimeError(f"ring barrier failed (rc={rc})")
        return None
