"""Native TCP ring collectives backend (reference parity:
ops/gloo_operations.{h,cc} — the CPU data plane).  Correctness across
op types, dtypes, process-set subgroups, ragged allgather, and
payloads large enough to cross the duplex-threading threshold."""

import pytest

from multiproc import assert_all_ok, run_workers

_RING_CHECK = """
from horovod_tpu.common import basics
state = basics._state()
assert type(state.backend).__name__ == "RingBackend", type(state.backend)
"""


def test_ring_is_default_cpu_backend():
    results = run_workers(_RING_CHECK + """
print("OK")
""", nproc=2)
    assert_all_ok(results)


def test_ring_ops_correctness_nproc3():
    results = run_workers(_RING_CHECK + """
import numpy as np

# allreduce across ops and dtypes (f32/f64/i32/i64 native; f16/bf16
# upcast; bool falls back to the XLA path)
for dt in (np.float32, np.float64, np.int32, np.int64, np.float16):
    x = (np.arange(5) + RANK + 1).astype(dt)
    y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"s.{dt.__name__}"))
    exp = (np.arange(5)[None, :] + np.arange(1, SIZE + 1)[:, None]).sum(0)
    np.testing.assert_allclose(y.astype(np.float64), exp, rtol=1e-3)

y = np.asarray(hvd.allreduce(np.full(4, float(RANK + 1), np.float32),
                             op=hvd.Max, name="mx"))
np.testing.assert_allclose(y, SIZE)
y = np.asarray(hvd.allreduce(np.full(4, 2.0, np.float32),
                             op=hvd.Product, name="pr"))
np.testing.assert_allclose(y, 2.0 ** SIZE)
y = np.asarray(hvd.allreduce(np.full(4, float(RANK), np.float32),
                             op=hvd.Average, name="av"))
np.testing.assert_allclose(y, (SIZE - 1) / 2.0)
y = np.asarray(hvd.allreduce(np.array([RANK % 2 == 0, True]),
                             op=hvd.Min, name="bool"))
np.testing.assert_array_equal(y, [False, True])

# ragged allgather: rank r contributes r+1 rows
g = np.asarray(hvd.allgather(
    np.full((RANK + 1, 3), float(RANK), np.float32), name="ag"))
assert g.shape == (SIZE * (SIZE + 1) // 2, 3), g.shape
off = 0
for r in range(SIZE):
    np.testing.assert_allclose(g[off:off + r + 1], float(r))
    off += r + 1

# broadcast from a non-zero root
b = np.asarray(hvd.broadcast(
    np.full(6, float(RANK * 10), np.float32), root_rank=2, name="bc"))
np.testing.assert_allclose(b, 20.0)

# large payload (crosses the 4MB duplex-thread threshold)
big = np.full(3 * 1024 * 1024, float(RANK + 1), np.float32)  # 12 MB
y = np.asarray(hvd.allreduce(big, op=hvd.Sum, name="big"))
np.testing.assert_allclose(y[:4], sum(range(1, SIZE + 1)))
np.testing.assert_allclose(y[-4:], sum(range(1, SIZE + 1)))

# scalar broadcast keeps its 0-d shape (regression: ascontiguousarray
# promoted 0-d to 1-d, breaking keras iteration-counter broadcast)
sc = np.asarray(hvd.broadcast(np.int64(5 if RANK == 0 else 0),
                              root_rank=0, name="scalar"))
assert sc.shape == () and int(sc) == 5, (sc.shape, sc)

# barrier completes
hvd.barrier()
assert state.backend.stats["ring_allreduces"] > 0
print("OK")
""", nproc=3, timeout=240)
    assert_all_ok(results)


def test_ring_alltoall_reducescatter_nproc3():
    results = run_workers(_RING_CHECK + """
import numpy as np

# Uneven alltoall: rank r sends r+d+1 rows to destination d. Row r of
# the split matrix is rank r's send vector; rank me receives column me.
splits = np.array([RANK + d + 1 for d in range(SIZE)], np.int64)
x = np.concatenate([
    np.full((int(s), 2), 10.0 * RANK + d, np.float32)
    for d, s in enumerate(splits)])
out, rsplits = hvd.alltoall(x, splits=splits, name="a2a")
out = np.asarray(out)
exp_rsplits = np.array([r + RANK + 1 for r in range(SIZE)], np.int64)
np.testing.assert_array_equal(np.asarray(rsplits), exp_rsplits)
off = 0
for r, s in enumerate(exp_rsplits):
    np.testing.assert_allclose(out[off:off + s], 10.0 * r + RANK)
    off += int(s)
assert out.shape == (int(exp_rsplits.sum()), 2), out.shape

# Even alltoall with splits=None (rows divisible by SIZE)
y = np.asarray(hvd.alltoall(
    np.repeat(np.arange(SIZE, dtype=np.float32), 2)[:, None],
    name="a2a_even"))
np.testing.assert_allclose(y.ravel(), np.repeat(float(RANK), 2 * SIZE))

# int alltoall rides the same raw-bytes path
z, _ = hvd.alltoall(np.full((SIZE, 1), RANK, np.int64),
                    splits=np.ones(SIZE, np.int64), name="a2a_int")
np.testing.assert_array_equal(np.asarray(z).ravel(), np.arange(SIZE))

# reducescatter: 7 rows over 3 ranks -> counts (3, 2, 2)
rows = 2 * SIZE + 1
x = np.tile(np.arange(rows, dtype=np.float32)[:, None], (1, 3))
mine = np.asarray(hvd.reducescatter(x, op=hvd.Sum, name="rs"))
base, rem = divmod(rows, SIZE)
counts = [base + (1 if r < rem else 0) for r in range(SIZE)]
start = sum(counts[:RANK])
exp = SIZE * np.tile(
    np.arange(start, start + counts[RANK], dtype=np.float32)[:, None],
    (1, 3))
np.testing.assert_allclose(mine, exp)
assert mine.shape == (counts[RANK], 3), mine.shape

# Average + f16 upcast path
m = np.asarray(hvd.reducescatter(
    np.full((SIZE, 4), float(RANK + 1), np.float16), op=hvd.Average,
    name="rs_avg"))
np.testing.assert_allclose(m.astype(np.float64),
                           (SIZE + 1) / 2.0, rtol=1e-3)

# Fused multi-tensor reduce-scatter: both tensors ride one ring pass
# (direct backend call — the runtime passes fused batches the same way)
pre = state.backend.stats.get("ring_reducescatters", 0)
outs = state.backend.reducescatter(
    [np.ones((SIZE, 2), np.float32),
     np.arange(2 * SIZE, dtype=np.float32).reshape(2 * SIZE, 1)],
    "Sum")
np.testing.assert_allclose(outs[0], SIZE * np.ones((1, 2)))
np.testing.assert_allclose(
    outs[1].ravel(), SIZE * np.arange(2 * RANK, 2 * RANK + 2))
assert state.backend.stats["ring_reducescatters"] == pre + 2

# A bad splits vector is a Python error before any native call
# (not an OOB read/write in C).
err = None
try:
    hvd.alltoall(np.zeros((4, 1), np.float32),
                 splits=np.full(SIZE, 2, np.int64), name="a2a_bad")
except Exception as e:
    err = e
assert err is not None and "sum to the first" in str(err), err

# Both ops ran on the native ring, not the XLA fallback.
assert state.backend.stats.get("ring_alltoalls", 0) >= 3, \
    state.backend.stats
assert state.backend.stats.get("ring_reducescatters", 0) >= 2, \
    state.backend.stats
print("OK")
""", nproc=3, timeout=240)
    assert_all_ok(results)


def test_ring_alltoall_process_set():
    results = run_workers(_RING_CHECK + """
import numpy as np
ps = hvd.add_process_set([0, 2])
if RANK in (0, 2):
    out, rsplits = hvd.alltoall(
        np.full((2, 1), float(RANK), np.float32),
        splits=np.ones(2, np.int64), name="ps_a2a", process_set=ps)
    np.testing.assert_array_equal(np.asarray(rsplits), [1, 1])
    np.testing.assert_allclose(np.asarray(out).ravel(), [0.0, 2.0])
    mine = np.asarray(hvd.reducescatter(
        np.ones((2, 2), np.float32), op=hvd.Sum, name="ps_rs",
        process_set=ps))
    np.testing.assert_allclose(mine, 2.0)
    assert mine.shape == (1, 2), mine.shape
print("OK")
""", nproc=3, timeout=240)
    assert_all_ok(results)


def test_ring_process_set_subgroup():
    results = run_workers(_RING_CHECK + """
import numpy as np
ps = hvd.add_process_set([0, 2])
if RANK in (0, 2):
    y = np.asarray(hvd.allreduce(np.full(4, float(RANK + 1), np.float32),
                                 op=hvd.Sum, name="sub",
                                 process_set=ps))
    np.testing.assert_allclose(y, 4.0)   # ranks 0 and 2: 1 + 3
    g = np.asarray(hvd.allgather(np.full((1, 2), float(RANK), np.float32),
                                 name="subg", process_set=ps))
    assert g.shape == (2, 2), g.shape
# world op afterwards still works
y = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                             name="world"))
np.testing.assert_allclose(y, SIZE)
print("OK")
""", nproc=3, timeout=240)
    assert_all_ok(results)


def test_cpu_operations_knob_forces_xla():
    results = run_workers("""
from horovod_tpu.common import basics
assert type(basics._state().backend).__name__ == "XlaMeshBackend"
y = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                             name="t"))
np.testing.assert_allclose(y, SIZE)
print("OK")
""", nproc=2, extra_env={"HOROVOD_CPU_OPERATIONS": "XLA"})
    assert_all_ok(results)


def test_jax_array_roundtrip_stays_jax():
    results = run_workers(_RING_CHECK + """
import jax.numpy as jnp
import jax
x = jnp.ones(8, jnp.float32) * (RANK + 1)
y = hvd.allreduce(x, op=hvd.Sum, name="jx")
assert isinstance(y, jax.Array), type(y)
np.testing.assert_allclose(np.asarray(y), 3.0)
print("OK")
""", nproc=2)
    assert_all_ok(results)


def test_ring_failure_demotes_all_ranks_together():
    """One rank failing ring setup must demote EVERY rank to the XLA
    fallback promptly (unanimous two-round agreement) — mixed backends
    would deadlock at the first collective.  Injection rides the
    failpoints subsystem (`ring.setup` site, rank predicate)."""
    import time
    t0 = time.monotonic()
    results = run_workers("""
from horovod_tpu.common import basics
assert type(basics._state().backend).__name__ == "XlaMeshBackend", \\
    type(basics._state().backend)
y = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                             name="t"))
np.testing.assert_allclose(y, SIZE)
print("OK")
""", nproc=3, timeout=240,
        extra_env={"HOROVOD_FAILPOINTS": "ring.setup=error(rank=1)"})
    assert_all_ok(results)
    # Prompt demotion: the healthy ranks observed the FAIL marker via
    # the agreement rounds instead of waiting out a 60s KV timeout.
    assert time.monotonic() - t0 < 120


def test_ring_survives_shutdown_reinit():
    """Elastic resets shutdown+init in-process with the same launcher
    endpoints: the ring must come back (keys were deleted at close, so
    the second incarnation's rendezvous starts clean)."""
    results = run_workers(_RING_CHECK + """
y = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                             name="a"))
np.testing.assert_allclose(y, SIZE)
hvd.shutdown()
hvd.init()
state = basics._state()
assert type(state.backend).__name__ == "RingBackend", type(state.backend)
y = np.asarray(hvd.allreduce(np.full(4, 2.0, np.float32), op=hvd.Sum,
                             name="b"))
np.testing.assert_allclose(y, 2.0 * SIZE)
print("REINIT OK")
""", nproc=2, timeout=240)
    assert_all_ok(results)


def test_ring_shm_active_and_correct_on_localhost():
    """All ranks share one host, so same-host hops must ride the
    shared-memory channels (collectives.cc ShmChan — the analog of
    the reference's on-host transports, gloo allreduce_local / MPI
    vader BTL); the op matrix must agree with TCP's results."""
    results = run_workers(_RING_CHECK + """
import numpy as np
assert state.backend.stats.get("ring_shm") is True, \\
    state.backend.stats

for dt in (np.float32, np.int64):
    x = (np.arange(7) + RANK + 1).astype(dt)
    y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"sh.{dt.__name__}"))
    exp = (np.arange(7)[None, :] + np.arange(1, SIZE + 1)[:, None]).sum(0)
    np.testing.assert_allclose(y.astype(np.float64), exp)

# Big payload streams through the bounded channel window (chunk size
# n/p exceeds HOROVOD_RING_SHM_CAP, so push/pop must interleave).
big = np.full(3 * 1024 * 1024, float(RANK + 1), np.float32)  # 12 MB
y = np.asarray(hvd.allreduce(big, op=hvd.Sum, name="sh.big"))
np.testing.assert_allclose(y[:4], sum(range(1, SIZE + 1)))
np.testing.assert_allclose(y[-4:], sum(range(1, SIZE + 1)))

g = np.asarray(hvd.allgather(
    np.full((RANK + 1, 2), float(RANK), np.float32), name="sh.ag"))
assert g.shape == (SIZE * (SIZE + 1) // 2, 2), g.shape

b = np.asarray(hvd.broadcast(np.full(5, float(RANK * 3), np.float32),
                             root_rank=1, name="sh.bc"))
np.testing.assert_allclose(b, 3.0)
hvd.barrier()
print("OK")
""", nproc=3, timeout=240)
    assert_all_ok(results)


def test_ring_shm_disabled_falls_back_to_tcp():
    """HOROVOD_RING_SHM=0 keeps every hop on the TCP sockets (the
    cross-host code path, exercised on localhost)."""
    results = run_workers(_RING_CHECK + """
import numpy as np
assert state.backend.stats.get("ring_shm") is False, \\
    state.backend.stats
y = np.asarray(hvd.allreduce(np.full(4, float(RANK + 1), np.float32),
                             op=hvd.Sum, name="tcp"))
np.testing.assert_allclose(y, sum(range(1, SIZE + 1)))
print("OK")
""", nproc=2, timeout=240, extra_env={"HOROVOD_RING_SHM": "0"})
    assert_all_ok(results)


def test_ring_shm_env_asymmetry_disables_everywhere():
    """One rank launched with HOROVOD_RING_SHM=0 must cost every rank
    the shm optimization — never a hang (a rank writing shm while its
    neighbor reads TCP would wedge the first collective)."""
    results = run_workers(_RING_CHECK + """
import numpy as np
assert state.backend.stats.get("ring_shm") is False, \\
    state.backend.stats
y = np.asarray(hvd.allreduce(np.full(4, float(RANK + 1), np.float32),
                             op=hvd.Sum, name="asym"))
np.testing.assert_allclose(y, sum(range(1, SIZE + 1)))
print("OK")
""", nproc=2, timeout=240,
        per_rank_env=lambda r: {"HOROVOD_RING_SHM": "0"} if r == 1
        else {})
    assert_all_ok(results)


def test_ring_shm_misaligned_wrap_reduce():
    """Regression: byte-granular ops (allgather) leave the channel
    tail misaligned relative to later element sizes; a large f64
    allreduce must then reassemble elements straddling the ring wrap
    (shm_pop_reduce stack bounce) instead of smearing garbage.  A
    4 KB channel window forces many wraps per op."""
    results = run_workers(_RING_CHECK + """
import numpy as np
assert state.backend.stats.get("ring_shm") is True, state.backend.stats

# Misalign: 28-byte-per-rank allgather (7 f32) shifts the tail by 4.
g = np.asarray(hvd.allgather(np.full(7, float(RANK), np.float32),
                             name="mis.ag"))
assert g.shape == (7 * SIZE,), g.shape

# Now a big f64 allreduce: chunks cross the 4 KB wrap dozens of
# times with tail % 8 == 4.  Exact integer-valued doubles make any
# smeared byte show up as a wrong value.
x = (np.arange(8192, dtype=np.float64) + 1000.0 * RANK)
y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="mis.f64"))
exp = SIZE * np.arange(8192, dtype=np.float64) + \\
    1000.0 * sum(range(SIZE))
np.testing.assert_array_equal(y, exp)

# And again with f32 after re-misaligning by 12 bytes.
g = np.asarray(hvd.allgather(np.full(3, 1.0, np.float32),
                             name="mis.ag2"))
x = np.full(6000, float(RANK + 1), np.float32)
y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="mis.f32"))
np.testing.assert_array_equal(y, float(sum(range(1, SIZE + 1))))
print("OK")
""", nproc=2, timeout=240,
        extra_env={"HOROVOD_RING_SHM_CAP": "4096"})
    assert_all_ok(results)


def test_ring_shm_peer_death_fails_promptly():
    """A same-host peer that hard-dies mid-transfer must surface as a
    prompt collective failure on the survivor (the shm wait watches
    the pair's idle TCP socket for EOF — Backoff.fd_dead), never a
    multi-minute timeout: elastic recovery latency depends on it."""
    import time
    t0 = time.monotonic()
    results = run_workers(_RING_CHECK + """
import os, threading, time
import numpy as np
from horovod_tpu.common.exceptions import HorovodInternalError

assert state.backend.stats.get("ring_shm") is True, state.backend.stats
# Warm the plane so the death happens on an established ring.
np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="w"))

big = np.full(16 * 1024 * 1024, float(RANK + 1), np.float32)  # 64 MB
if RANK == 1:
    threading.Timer(0.05, lambda: os._exit(1)).start()
t0 = time.perf_counter()
try:
    np.asarray(hvd.allreduce(big, op=hvd.Sum, name="die"))
    assert RANK == 1, "survivor's collective unexpectedly succeeded"
except Exception as e:
    dt = time.perf_counter() - t0
    print("FAILED-FAST %.1fs %s" % (dt, type(e).__name__), flush=True)
    assert dt < 30, "detection took %.1fs" % dt
print("OK")
""", nproc=2, timeout=240,
        # The survivor's exit waits in jax.distributed's shutdown
        # barrier until the dead peer's heartbeat times out (100 s by
        # default); that wait is not what this test is about.
        extra_env={"HOROVOD_RING_SHM_CAP": "65536",
                   "HOROVOD_JAX_HEARTBEAT_TIMEOUT": "10"})
    # Rank 1 exits 1 by design.  Rank 0 must observe the failure as a
    # raised collective error well inside the 300 s shm timeout; its
    # own exit code may be nonzero too (the job is aborted — shutdown
    # after a dead peer is fatal-to-job by design, and elastic catches
    # HorovodInternalError above this layer).
    elapsed = time.monotonic() - t0
    rank0 = results[0]
    assert "FAILED-FAST" in rank0[1] and "OK" in rank0[1], rank0
    assert elapsed < 120, "survivor took %.0fs — death not detected" \
        % elapsed
