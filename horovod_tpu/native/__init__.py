"""Native control-plane core: ctypes loader and build for the C++
coordinator (the analog of the reference's compiled C++ core that
``HorovodBasics`` loads, reference: common/basics.py:22-30 — here the
binding is ctypes over a plain C API instead of per-framework extension
modules).

The library builds lazily with g++ on first use (a few seconds, cached
under ``native/build/`` beside a hash of the sources it was built
from); when no toolchain is available everything falls back to the
pure-Python implementations, except on a TPU, where the coordinator
raises (``common/controller_net.py``).
Set ``HOROVOD_TPU_NATIVE=0`` to force the Python paths.
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import sys
import threading

logger = logging.getLogger("horovod_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "coordinator.cc")
_SRC_COLL = os.path.join(_DIR, "collectives.cc")
_BUILD_DIR = os.path.join(_DIR, "build")
_LIB = os.path.join(_BUILD_DIR, "libhvdtpu_coord.so")
_LIB_HASH = _LIB + ".sha256"

_lock = threading.Lock()
_lib = None
_tried = False


def enabled() -> bool:
    from ..common import env as env_mod
    return env_mod.env_str("HOROVOD_TPU_NATIVE", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def _sources_hash(srcs) -> str:
    h = hashlib.sha256()
    for path in srcs:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_hash() -> str:
    try:
        with open(_LIB_HASH) as f:
            return f.read().strip()
    except OSError:
        return ""


def ensure_built(force: bool = False) -> bool:
    """Compile the shared library if missing/stale; returns success.

    Stale is judged by content: the library is kept only while the hash
    written beside it equals the hash of the sources.  File times say
    nothing after a copy of the tree."""
    if not os.path.exists(_SRC):
        return False
    srcs = [_SRC]
    if os.path.exists(_SRC_COLL):
        srcs.append(_SRC_COLL)
    want = _sources_hash(srcs)
    if not force and os.path.exists(_LIB) and _built_hash() == want:
        return True
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Unique tmp per process: concurrent builders (multi-proc tests
    # racing a stale library) must never interleave writes into one tmp
    # file — each builds privately, the atomic replace makes the last
    # one win with a complete .so either way.
    tmp = "%s.tmp.%d" % (_LIB, os.getpid())
    # -lrt: shm_open/shm_unlink (collectives.cc's same-host shm data
    # plane) live in librt until glibc 2.34; linking a shared object
    # leaves them silently unresolved, so without this the build
    # "succeeds" and dlopen fails at first load.
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
           *srcs, "-o", tmp]
    if sys.platform.startswith("linux"):
        cmd.append("-lrt")  # macOS/musl have shm_open in libc, no librt
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        with open(tmp, "w") as f:
            f.write(want)
        os.replace(tmp, _LIB_HASH)
        logger.info("built native coordinator: %s", _LIB)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        err = getattr(e, "stderr", b"")
        logger.warning("native coordinator build failed (%s); using the "
                       "Python coordinator", (err or b"")[:500])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load():
    """Returns the loaded CDLL or None."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not enabled():
            return None
        if not ensure_built():
            return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            # A cached .so from an older build recipe (or another
            # glibc) can be unloadable while looking fresh by mtime —
            # rebuild once before falling back to Python.
            logger.warning("could not load %s; rebuilding", _LIB,
                           exc_info=True)
            if not ensure_built(force=True):
                return None
            try:
                lib = ctypes.CDLL(_LIB)
            except OSError:
                logger.warning("could not load %s", _LIB, exc_info=True)
                return None
        lib.hvd_coord_create.restype = ctypes.c_void_p
        lib.hvd_coord_create.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_double, ctypes.c_double]
        lib.hvd_coord_port.restype = ctypes.c_int
        lib.hvd_coord_port.argtypes = [ctypes.c_void_p]
        lib.hvd_coord_set_fusion.argtypes = [ctypes.c_void_p,
                                             ctypes.c_longlong]
        lib.hvd_coord_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong)]
        lib.hvd_coord_cache_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong)]
        lib.hvd_coord_drain_round_bytes.restype = ctypes.c_int
        lib.hvd_coord_drain_round_bytes.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_int]
        lib.hvd_coord_stall_report.restype = ctypes.c_int
        lib.hvd_coord_stall_report.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.hvd_coord_stop.argtypes = [ctypes.c_void_p]
        lib.hvd_coord_counts.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


class NativeCoordinatorServer:
    """Drop-in replacement for controller_net.CoordinatorServer backed
    by the C++ library.  When an autotuning ParameterManager is given, a
    poll thread feeds it the coordinator's live round/byte counters and
    pushes retuned fusion thresholds back."""

    POLL_INTERVAL_S = 0.1

    def __init__(self, size: int, bind_addr: str = "0.0.0.0",
                 port: int = 0, fusion_threshold: int = 64 << 20,
                 elastic: bool = False,
                 allow_ephemeral_fallback: bool = False,
                 param_manager=None, cache_capacity: int = 1024,
                 stall_warning_time_s: float = 60.0,
                 stall_shutdown_time_s: float = 0.0):
        lib = load()
        if lib is None:
            raise RuntimeError("native coordinator unavailable")
        self._lib = lib
        self._handle = lib.hvd_coord_create(
            size, bind_addr.encode(), port, fusion_threshold,
            1 if elastic else 0, 1 if allow_ephemeral_fallback else 0,
            cache_capacity, stall_warning_time_s, stall_shutdown_time_s)
        if not self._handle:
            raise OSError(
                f"native coordinator could not bind port {port}")
        self.port = lib.hvd_coord_port(self._handle)
        self.param_manager = param_manager
        self._stop = threading.Event()
        self._poll_thread = None
        if param_manager is not None:
            self._poll_thread = threading.Thread(
                target=self._poll_loop, name="hvd-native-autotune",
                daemon=True)
            self._poll_thread.start()

    def drain_round_bytes(self, cap: int = 1024):
        """All per-round fused-byte values committed since the last
        drain (single consumer: the autotune poll thread, or a test)."""
        buf = (ctypes.c_longlong * cap)()
        vals = []
        while True:
            n = self._lib.hvd_coord_drain_round_bytes(
                self._handle, buf, cap)
            vals.extend(buf[:n])
            if n < cap:
                return vals

    def _poll_loop(self):
        # Drain the coordinator's per-round byte ring so the GP sees
        # the true per-round distribution, not a window average
        # (reference feeds the tuner per-cycle scores,
        # parameter_manager.cc Update()).
        while not self._stop.wait(self.POLL_INTERVAL_S):
            if not self.param_manager.active:
                return
            vals = self.drain_round_bytes()
            for v in vals:
                self.param_manager.record_step(v)
            if vals:
                self._lib.hvd_coord_set_fusion(
                    self._handle,
                    self.param_manager.fusion_threshold_bytes)

    def departure_counts(self):
        """(ever_connected, departed) rank-connection counters."""
        if not self._handle:
            return 0, 0
        seen = ctypes.c_int()
        departed = ctypes.c_int()
        self._lib.hvd_coord_counts(self._handle, ctypes.byref(seen),
                                   ctypes.byref(departed))
        return seen.value, departed.value

    def cache_stats(self):
        """(fast_rounds, full_rounds) response-cache round counters."""
        if not self._handle:
            return 0, 0
        fast = ctypes.c_longlong()
        full = ctypes.c_longlong()
        self._lib.hvd_coord_cache_stats(self._handle, ctypes.byref(fast),
                                        ctypes.byref(full))
        return fast.value, full.value

    def stall_report(self) -> str:
        """Coordinator-side stall attribution text ('' = no stalls)."""
        if not self._handle:
            return ""
        buf = ctypes.create_string_buffer(65536)
        n = self._lib.hvd_coord_stall_report(self._handle, buf, len(buf))
        return buf.raw[:n].decode(errors="replace")

    def stop(self):
        self._stop.set()
        # Join the poll thread BEFORE freeing the C++ object: a poll
        # mid-flight would read freed memory.
        if self._poll_thread is not None:
            self._poll_thread.join(timeout=2.0)
            self._poll_thread = None
        if self._handle:
            self._lib.hvd_coord_stop(self._handle)
            self._handle = None
