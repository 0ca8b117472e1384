"""Protocol soak: high op rate, mixed op kinds, overlapping process
sets, grouped ops, and async handles through the real controller at
nproc=4 — the churn profile that historically surfaced ordering and
shutdown races (rounds 3-5 each found one).  Reference analog: the
high-iteration parameterized sweeps in test/parallel/test_tensorflow.py.
"""

import pytest

from multiproc import assert_all_ok, run_workers


def test_protocol_soak_nproc4():
    results = run_workers("""
import numpy as np

ps_even = hvd.ProcessSet([0, 2])
ps_odd = hvd.ProcessSet([1, 3])
hvd.init(process_sets=[ps_even, ps_odd])
mine = ps_even if RANK % 2 == 0 else ps_odd

for it in range(60):
    # World allreduce (cache hit after round 1).
    y = np.asarray(hvd.allreduce(np.full(257, float(RANK + 1),
                                         np.float32),
                                 op=hvd.Sum, name="w%d" % (it % 7)))
    np.testing.assert_allclose(y, sum(range(1, SIZE + 1)))

    # Subgroup allreduce on the overlapping process sets.
    z = np.asarray(hvd.allreduce(np.full(33, 1.0, np.float32),
                                 op=hvd.Sum, name="ps%d" % (it % 5),
                                 process_set=mine))
    np.testing.assert_allclose(z, 2.0)

    # Grouped (atomic fusion), alternating sizes.
    g = hvd.grouped_allreduce(
        [np.full(8 + (it % 3), float(RANK), np.float32),
         np.full(5, 2.0, np.float32)],
        op=hvd.Average, name="g%d" % (it % 4))
    np.testing.assert_allclose(np.asarray(g[1]), 2.0)

    # Async pipeline: several handles in flight at once.
    hs = [hvd.allreduce_async(np.full(16, float(i), np.float32),
                              op=hvd.Sum, name="a%d.%d" % (it % 3, i))
          for i in range(4)]
    for i, h in enumerate(hs):
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   SIZE * float(i))

    # Uneven allgather + alltoall churn.
    if it % 4 == 0:
        out = np.asarray(hvd.allgather(
            np.full((RANK + 1, 2), float(RANK), np.float32),
            name="ag%d" % it))
        assert out.shape == (SIZE * (SIZE + 1) // 2, 2)
    if it % 5 == 0:
        splits = np.array([RANK + d + 1 for d in range(SIZE)],
                          np.int64)
        x = np.arange(int(splits.sum()), dtype=np.float32)
        hvd.alltoall(x, splits=splits, name="at%d" % it)

hvd.barrier()
print("SOAK OK rank=%d" % RANK)
""", nproc=4, timeout=240)
    assert_all_ok(results)


@pytest.mark.parametrize("plane", ["RING", "XLA"])
def test_same_name_on_two_process_sets_concurrently(plane):
    """Regression: the SAME tensor name in flight on two disjoint
    process sets at once.  The reference supports this structurally
    (each process set owns its own controller); a name-only message
    table mixed the two negotiations and wedged both sets — all
    coordinator state is now keyed (process_set_id, name), Python and
    C++ coordinators alike.  Parametrized over both eager data planes
    (native ring incl. shm, XLA mesh)."""
    results = run_workers("""
import numpy as np

ps_even = hvd.ProcessSet([0, 2])
ps_odd = hvd.ProcessSet([1, 3])
hvd.init(process_sets=[ps_even, ps_odd])
mine = ps_even if RANK % 2 == 0 else ps_odd
other_val = float(RANK + 1)

for it in range(8):
    # Identical name, different sets, different shapes AND dtypes:
    # any cross-set mixing would trip the mismatch validator or hang.
    if RANK % 2 == 0:
        x = np.full(5, other_val, np.float32)
        exp = 1.0 + 3.0
    else:
        x = np.full(9, other_val, np.float64)
        exp = 2.0 + 4.0
    y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="shared",
                                 process_set=mine))
    np.testing.assert_allclose(y, exp)
hvd.barrier()
print("OK rank=%d" % RANK)
""", nproc=4, timeout=240,
        extra_env={"HOROVOD_CPU_OPERATIONS": plane})
    assert_all_ok(results)


def test_unregistered_process_set_raises():
    """A process set never registered (not passed to init, no
    add_process_set) must fail fast with a clear error, not send a
    colliding psid=-1 request."""
    results = run_workers("""
import numpy as np
ps = hvd.ProcessSet([0, 1])
try:
    hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="x",
                  process_set=ps)
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "not registered" in str(e), e
print("OK rank=%d" % RANK)
""", nproc=2, timeout=240)
    assert_all_ok(results)


def test_formation_stall_attributed_and_failed():
    """A rank that never connects must be attributed and, past the
    shutdown threshold, the buffered collectives must FAIL on the
    connected ranks — not hang silently (pre-formation requests bypass
    the per-tensor stall table).  Driven at the protocol level: real
    CoordinatorServer, socketpair stand-ins for two of three ranks."""
    import socket
    import struct
    import time

    from horovod_tpu.common.controller_net import (CoordinatorServer,
                                                   _recv_frame,
                                                   _send_frame)
    from horovod_tpu.common.message import (DataType, Request,
                                            RequestType,
                                            unpack_response_list)

    srv = CoordinatorServer(3, port=0, fusion_threshold=1 << 20,
                            stall_warning_time_s=0.2,
                            stall_shutdown_time_s=0.6)
    try:
        conns = []
        for rank in (0, 1):
            c = socket.create_connection(("127.0.0.1", srv.port))
            _send_frame(c, b"RQ", struct.pack("<i", rank))  # registration is an RQ frame (frame-parity rule)
            conns.append(c)
        # Let the hello frames register (accept thread).
        deadline = time.monotonic() + 5
        while srv.departure_counts()[0] < 2 and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        req = Request(request_rank=0,
                      request_type=RequestType.ALLREDUCE,
                      tensor_name="never", tensor_shape=(4,),
                      tensor_type=DataType.FLOAT32)
        srv._handle_requests(0, [req])
        assert srv._pre_formed, "request was not gated on formation"
        # The stall loop must fail the buffered request within the
        # shutdown threshold (+ slack): rank 0 receives an ERROR
        # response naming the unconnected ranks.
        conns[0].settimeout(10)
        frame = _recv_frame(conns[0])
        assert frame is not None, "no error frame before timeout"
        magic, payload = frame
        assert magic == b"RS", magic
        responses, _ = unpack_response_list(payload)
        assert responses and responses[0].error_message, responses
        assert "never connected" in responses[0].error_message, \
            responses[0].error_message
        assert responses[0].tensor_names == ["never"]
        for c in conns:
            c.close()
    finally:
        srv.stop()


def test_a_stopped_coordinator_frees_its_port_at_once():
    """``hvd.shutdown()`` then ``hvd.init()``: rank 0's next
    incarnation binds the port of the last one a few milliseconds
    after ``stop()``.  The stopped server's socket must not go on
    listening while its accept thread's poll runs out (PR 28:
    ``EADDRINUSE`` in ``test_init_shutdown_churn_nproc3``)."""
    import time

    from horovod_tpu.common.controller_net import CoordinatorServer

    srv = CoordinatorServer(3, port=0)
    port = srv.port
    for _ in range(5):
        time.sleep(0.1)     # the accept thread is in its poll
        srv.stop()
        srv = CoordinatorServer(3, port=port)
    srv.stop()


def test_init_shutdown_churn_nproc3():
    """Repeated shutdown+init cycles with collectives in between: each
    incarnation re-forms the controller, ring (incl. the shm segment,
    which must unlink and re-create cleanly), and response cache under
    fresh incarnation-scoped namespaces.  Catches cross-incarnation
    leakage the single-cycle reinit test cannot."""
    results = run_workers("""
import numpy as np
import glob

pre_existing = set(glob.glob("/dev/shm/hvdring*"))
for cycle in range(4):
    if cycle:
        hvd.init()
    for step in range(3):
        y = np.asarray(hvd.allreduce(
            np.full(64, float(RANK + 1), np.float32), op=hvd.Sum,
            name="c%d.s%d" % (cycle, step)))
        np.testing.assert_allclose(y, sum(range(1, SIZE + 1)))
    # Same op name EVERY cycle: a stale response cache or shm channel
    # state crossing incarnations would corrupt or wedge this.
    y = np.asarray(hvd.allreduce(np.full(8, 1.0, np.float32),
                                 op=hvd.Sum, name="stable"))
    np.testing.assert_allclose(y, SIZE)
    hvd.barrier()
    hvd.shutdown()
# Only segments THIS test's incarnations created count: /dev/shm is
# host-global and other jobs' files are not ours to assert about.
leftover = set(glob.glob("/dev/shm/hvdring*")) - pre_existing
print("CHURN OK rank=%d leftover=%d" % (RANK, len(leftover)))
""", nproc=3, timeout=240)
    assert_all_ok(results)
    for _, out in results:
        assert "CHURN OK" in out
        # All incarnations' shm segments must be unlinked by shutdown.
        assert "leftover=0" in out, out[-500:]

