"""Failpoint subsystem: grammar, determinism, predicate semantics, the
zero-overhead-when-disabled guarantee, and the site wiring that other
suites rely on (ring demotion is covered in test_ring_backend, chaos
recovery in test_chaos_smoke)."""

import time

import pytest

from horovod_tpu.common import failpoints as fp
from horovod_tpu.common import metrics


@pytest.fixture(autouse=True)
def _clean_registry():
    fp.reset()
    fp.set_crash_handler(None)
    fp.set_rank(None)
    yield
    fp.reset()
    fp.set_crash_handler(None)
    fp.set_rank(None)


# ---------------------------------------------------------------------------
# grammar
# ---------------------------------------------------------------------------

def test_parse_full_grammar():
    n = fp.configure(
        "ring.send=delay(50ms,p=0.1);coord.frame_recv=drop(1);"
        "elastic.worker=crash(rank=3,epoch=2);a.b=error(boom);"
        "c.d=partition(200ms,times=1)")
    assert n == 5 and fp.ENABLED
    assert fp.sites() == ["a.b", "c.d", "coord.frame_recv",
                          "elastic.worker", "ring.send"]
    snap = fp.snapshot()
    assert snap["ring.send"][0]["action"] == "delay"
    assert snap["elastic.worker"][0]["rank"] == 3
    assert snap["elastic.worker"][0]["epoch"] == 2


def test_empty_spec_disables():
    fp.configure("x.y=drop()")
    assert fp.ENABLED
    assert fp.configure("") == 0
    assert not fp.ENABLED


@pytest.mark.parametrize("bad", [
    "no_equals_sign", "site=unknown_action(1)", "site=drop(1",
    "site=drop(zorp=1)",
])
def test_malformed_spec_raises(bad):
    with pytest.raises(ValueError):
        fp.configure(bad)


def test_duration_suffixes():
    fp.configure("a.b=delay(10ms);c.d=delay(2s);e.f=delay(100us);"
                 "g.h=delay(0.25)")
    snap = fp.snapshot()
    assert snap["a.b"][0]["action"] == "delay"
    t0 = time.perf_counter()
    fp.maybe_fail("a.b")
    assert 0.005 < time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# action + predicate semantics
# ---------------------------------------------------------------------------

def test_drop_count_and_exhaustion():
    fp.configure("s.x=drop(2)")
    assert [fp.maybe_fail("s.x") for _ in range(4)] == \
        ["drop", "drop", None, None]


def test_after_skips_leading_evaluations():
    fp.configure("s.x=drop(1,after=2)")
    assert [fp.maybe_fail("s.x") for _ in range(4)] == \
        [None, None, "drop", None]


def test_error_raises_and_respects_times():
    fp.configure("s.x=error(kaboom,times=1)")
    with pytest.raises(fp.FailpointError, match="kaboom"):
        fp.maybe_fail("s.x")
    assert fp.maybe_fail("s.x") is None


def test_rank_predicate_context_beats_default():
    fp.configure("s.x=drop(rank=2)")
    assert fp.maybe_fail("s.x", rank=1) is None
    assert fp.maybe_fail("s.x", rank=2) == "drop"
    fp.set_rank(2)
    assert fp.maybe_fail("s.x") == "drop"
    assert fp.maybe_fail("s.x", rank=0) is None


def test_epoch_predicate():
    fp.configure("s.x=drop(epoch=3)")
    assert fp.maybe_fail("s.x", epoch=2) is None
    assert fp.maybe_fail("s.x", epoch=3) == "drop"


def test_crash_handler_override():
    seen = []
    fp.set_crash_handler(seen.append)
    fp.configure("s.x=crash(times=1)")
    assert fp.maybe_fail("s.x") == "crash"
    assert seen == ["s.x"]
    # crash_ok: the caller models the death; the handler must NOT run.
    fp.configure("s.y=crash()")
    assert fp.maybe_fail("s.y", crash_ok=True) == "crash"
    assert seen == ["s.x"]


def test_partition_window_drops_everything_then_closes():
    fp.configure("s.x=partition(150ms,times=1)")
    assert fp.maybe_fail("s.x") == "drop"
    assert fp.maybe_fail("s.x") == "drop"  # inside the window
    time.sleep(0.2)
    assert fp.maybe_fail("s.x") is None    # window closed, times spent


def test_seeded_prng_is_deterministic_and_seed_sensitive():
    def draw(seed):
        fp.configure("s.x=drop(p=0.4,times=100)", seed=seed)
        return [fp.maybe_fail("s.x") for _ in range(32)]

    a, b, c = draw(7), draw(7), draw(8)
    assert a == b
    assert a != c
    assert "drop" in a and None in a  # p actually partitions the draws


def test_rules_have_independent_streams():
    """A second rule on ANOTHER site must not perturb the first rule's
    schedule (each rule owns its own PRNG)."""
    fp.configure("s.x=drop(p=0.4,times=100)", seed=9)
    solo = [fp.maybe_fail("s.x") for _ in range(16)]
    fp.configure("s.x=drop(p=0.4,times=100);t.y=drop(p=0.9,times=100)",
                 seed=9)
    mixed = []
    for _ in range(16):
        mixed.append(fp.maybe_fail("s.x"))
        fp.maybe_fail("t.y")
    assert solo == mixed


def test_partition_window_counts_one_trigger():
    """Units swallowed by an open window are not fresh triggers: the
    exported counter must agree with snapshot(), not diverge by the
    evaluation rate."""
    c = metrics.REGISTRY.counter("hvd_failpoint_triggers_total")
    before = c.value(site="pw.x", action="partition")
    fp.configure("pw.x=partition(300ms,times=1)")
    for _ in range(10):
        assert fp.maybe_fail("pw.x") == "drop"
    assert c.value(site="pw.x", action="partition") - before == 1
    assert fp.snapshot()["pw.x"][0]["triggers"] == 1


def test_worker_frame_recv_error_breaks_not_hangs():
    """error() on worker.frame_recv must surface through the broken-
    connection path — blocked submitters fail fast — never die as a
    bare recv-thread exception that leaves them hanging (review
    finding on the unbounded-hang contract)."""
    import numpy as np

    from multiproc import assert_all_ok, run_workers

    results = run_workers("""
hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="warm")
try:
    for i in range(6):
        hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                      name="e%d" % i)
    raise SystemExit("injected downlink error never surfaced")
except Exception as e:
    assert "injected downlink" in str(e), repr(e)
print("FRAME-RECV-ERROR-OK rank=%d" % RANK)
""", nproc=2, timeout=240, extra_env={
        "HOROVOD_FAILPOINTS":
            "worker.frame_recv=error(injected downlink fault,"
            "times=1,after=2)",
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "2",
        "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "6",
    })
    assert_all_ok(results)


def test_coord_broadcast_error_degrades_to_drop():
    """error() on coord.broadcast must not kill the caller (the stall
    loop depends on broadcasting) — it degrades to a dropped frame."""
    import socket
    import struct
    import time as _time

    from horovod_tpu.common.controller_net import (CoordinatorServer,
                                                   _recv_frame,
                                                   _send_frame)
    from horovod_tpu.common.message import (DataType, Request,
                                            RequestType,
                                            pack_request_list,
                                            unpack_response_list)

    fp.configure("coord.broadcast=error(x,times=1)")
    srv = CoordinatorServer(2, port=0, fusion_threshold=1 << 20,
                            stall_warning_time_s=60.0)
    conns = []
    try:
        for rank in range(2):
            c = socket.create_connection(("127.0.0.1", srv.port))
            _send_frame(c, b"RQ", struct.pack("<i", rank))  # registration is an RQ frame (frame-parity rule)
            conns.append(c)
        deadline = _time.monotonic() + 5
        while srv.departure_counts()[0] < 2 and \
                _time.monotonic() < deadline:
            _time.sleep(0.02)

        def negotiate(name):
            for rank, c in enumerate(conns):
                _send_frame(c, b"RQ", pack_request_list([Request(
                    request_rank=rank,
                    request_type=RequestType.ALLREDUCE,
                    tensor_name=name, tensor_shape=(4,),
                    tensor_type=DataType.FLOAT32)]))

        # t1's RS broadcast hits the injected error → dropped (spending
        # the rule); in a real world the WORKER-side stall inspector
        # bounds that wedge.  What this asserts: the error must not
        # escape _broadcast_frame_locked and kill the rank loops — t2
        # must still negotiate and broadcast normally afterwards.
        negotiate("t1")
        negotiate("t2")
        conns[0].settimeout(10)
        frame = _recv_frame(conns[0])
        assert frame is not None, "coordinator died after the error"
        magic, payload = frame
        assert magic == b"RS"
        responses, _ = unpack_response_list(payload)
        assert responses[0].tensor_names == ["t2"]
        assert not responses[0].error_message
        assert fp.snapshot()["coord.broadcast"][0]["triggers"] == 1
    finally:
        for c in conns:
            c.close()
        srv.stop()


def test_trigger_metrics_exported():
    before = metrics.REGISTRY.counter(
        "hvd_failpoint_triggers_total").value(site="m.x", action="drop")
    fp.configure("m.x=drop(3)")
    for _ in range(5):
        fp.maybe_fail("m.x")
    after = metrics.REGISTRY.counter(
        "hvd_failpoint_triggers_total").value(site="m.x", action="drop")
    assert after - before == 3


# ---------------------------------------------------------------------------
# the zero-overhead-when-disabled guarantee
# ---------------------------------------------------------------------------

def test_disabled_sites_never_enter_the_registry(monkeypatch,
                                                 hvd_single):
    """With HOROVOD_FAILPOINTS unset every site must reduce to the
    single `failpoints.ENABLED` attribute check: run a real collective
    through the runtime with maybe_fail booby-trapped — if any site
    called past the flag, the collective would explode."""
    import numpy as np

    assert not fp.ENABLED

    def boom(*a, **k):
        raise AssertionError("maybe_fail called while disabled")

    monkeypatch.setattr(fp, "maybe_fail", boom)
    out = np.asarray(hvd_single.allreduce(
        np.ones(8, np.float32), op=hvd_single.Sum, name="fp.disabled"))
    np.testing.assert_allclose(out, 1.0)


def test_disabled_path_overhead_stays_one_attribute_check():
    """Perf pin: with HOROVOD_FAILPOINTS unset, a site costs ONE
    module-attribute check — tens of nanoseconds.  The absolute bound
    below is ~20x the measured cost on an idle rig, loose enough for
    CI noise but tight enough that reintroducing per-call work
    (registry lookup, rule matching, getattr chains — each ~10x the
    guard) fails immediately."""
    import timeit

    assert not fp.ENABLED
    n = 200_000
    per_call = timeit.timeit(
        "fp.ENABLED and fp.maybe_fail('perf.site')",
        globals={"fp": fp}, number=n) / n
    assert per_call < 1e-6, \
        "disabled failpoint guard costs %.0f ns/op (>1 us): no " \
        "longer a bare attribute check" % (per_call * 1e9)


def test_enabled_site_fires_through_the_runtime(hvd_single):
    """The inverse control: with a runtime.submit rule armed, the same
    collective path must raise the injected error."""
    import numpy as np

    fp.configure("runtime.submit=error(injected,times=1)")
    with pytest.raises(Exception, match="injected"):
        hvd_single.allreduce(np.ones(4, np.float32),
                             op=hvd_single.Sum, name="fp.enabled")


def test_rendezvous_request_site():
    """drop() severs the connection (client retries see nothing);
    error() surfaces as HTTP 500."""
    from urllib.error import HTTPError

    from horovod_tpu.runner.http_server import (RendezvousClient,
                                                RendezvousServer)

    server = RendezvousServer(secret="")
    port = server.start()
    client = RendezvousClient("127.0.0.1", port, timeout=5.0, secret="")
    try:
        client.put("scope", "k", b"v")
        fp.configure("rendezvous.request=error(injected,times=1)")
        with pytest.raises(HTTPError) as exc:
            client.get("scope", "k")
        assert exc.value.code == 500
        # Rule spent: the store answers again, state intact.
        assert client.get("scope", "k") == b"v"
        fp.configure("rendezvous.request=drop(1)")
        with pytest.raises(OSError):
            client.get("scope", "k")
        assert client.get("scope", "k") == b"v"
    finally:
        fp.reset()
        server.stop()


def test_elastic_driver_worker_site_records_failure():
    """elastic.worker=crash on the driver spawn path must register as
    a worker failure (the registry sees exit-code-1 semantics), while
    the driver itself survives."""
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    launched = []

    fp.configure("elastic.worker=crash(rank=1,times=1)")
    driver = ElasticDriver(rendezvous=None,
                           discovery=FixedHosts({"localhost": 2}),
                           min_np=2, max_np=2, timeout=20)
    try:
        driver.start(2, lambda slot: launched.append(slot.rank) or 0)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            results = driver.get_results()
            if "localhost:1" in results:
                break
            time.sleep(0.05)
        results = driver.get_results()
        assert results.get("localhost:1") == 1, results
        assert 1 not in launched          # the crash preempted the fn
        assert 0 in launched              # healthy slot ran
        assert metrics.REGISTRY.counter(
            "hvd_elastic_worker_failures_total").value() >= 1
    finally:
        driver.stop()
        fp.reset()
