"""Steady-state replay (common/replay.py): engage/exit correctness and
the coalesced-frame protocol.

Tier-1 coverage for the round-6 fast path: converged cycles must
execute bit-identically with zero wire traffic, and EVERY exit reason
must fall back into a normal negotiation round that still produces the
right answer.  The tracker's state machine is unit-tested in-process
(fake runtime), the end-to-end behavior across real worker processes,
and the coalesced CH/RQ framing at 8 ranks against the coordinator
protocol directly (both coordinators; the native one skips when the
container has no C++ toolchain)."""

import socket
import struct
import time

import numpy as np
import pytest

from horovod_tpu.common import failpoints as fp
from horovod_tpu.common import metrics
from horovod_tpu.common.message import (DataType, Request, RequestType,
                                        Response, ResponseType,
                                        pack_bits, pack_request_list,
                                        unpack_bit_batches,
                                        unpack_response_list)
from horovod_tpu.common.replay import SteadyStateReplay
from horovod_tpu.common.response_cache import request_signature
from horovod_tpu.common.tensor_queue import TensorQueue

from multiproc import assert_all_ok, run_workers


# ---------------------------------------------------------------------------
# unit level: the tracker state machine against a fake runtime
# ---------------------------------------------------------------------------

class _FakeRuntime:
    def __init__(self):
        self.tensor_queue = TensorQueue()
        self.stall_inspector = None
        self.timeline = None
        self.executed = []
        self.woken = 0

    def replay_execute(self, resp):
        self.executed.append(list(resp.tensor_names))
        for name in resp.tensor_names:
            e = self.tensor_queue.pop_entry(name, resp.process_set_id)
            if e is not None:
                e.callback(True, None)

    def wake(self):
        self.woken += 1


def _req(name, shape=(4,)):
    return Request(request_rank=0, request_type=RequestType.ALLREDUCE,
                   tensor_name=name, tensor_shape=shape,
                   tensor_type=DataType.FLOAT32, reduce_op="Sum")


def _resp(names):
    return Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=list(names),
                    tensor_type=DataType.FLOAT32, reduce_op="Sum",
                    tensor_shapes=[(4,)] * len(names))


def _entry(name):
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    return TensorTableEntry(tensor_name=name,
                            tensor=np.zeros(4, np.float32),
                            callback=lambda ok, r: None)


def _drive_cycle(rp, names, kind="cb", bits=None):
    """One synchronous cycle: submit each name, deliver its response."""
    entered = False
    for i, name in enumerate(names):
        r = _req(name)
        if rp.active:
            assert rp.replay_submit(r, _entry(name))
            continue
        if rp.observe_submit(r):
            entered = True
            assert rp.replay_submit(r, _entry(name))
            continue
        rp.on_responses(kind, [(_resp([name]),
                                (bits or {}).get(name, (i,)))])
    return entered


def test_tracker_enters_after_warmup_and_replays():
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=3)
    names = ["u.a", "u.b"]
    for _ in range(3):
        assert not _drive_cycle(rp, names)
        assert not rp.active
    # 4th cycle: boundary submission sees 3 stable cycles -> replay.
    _drive_cycle(rp, names)
    assert rp.active
    assert rt.executed[-2:] == [["u.a"], ["u.b"]]
    before = len(rt.executed)
    _drive_cycle(rp, names)
    assert len(rt.executed) == before + 2
    assert metrics.REGISTRY.counter(
        "hvd_steady_state_cycles_replayed").value() >= 1


def test_tracker_exits_on_each_reason_and_flushes_partial_batch():
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["x.a", "x.b"]
    for _ in range(3):
        _drive_cycle(rp, names)
    assert rp.active

    # Unseen tensor: exit, and the request is NOT handled — the
    # caller (runtime.submit) falls through to negotiation with it.
    assert not rp.replay_submit(_req("x.new"), _entry("x.new"))
    assert not rp.active
    assert metrics.REGISTRY.counter("hvd_steady_state_exits").value(
        reason="unseen_tensor") >= 1

    # Re-converge, then signature change.
    for _ in range(3):
        _drive_cycle(rp, names)
    assert rp.active
    assert not rp.replay_submit(_req("x.a", shape=(8,)),
                                _entry("x.a"))
    assert metrics.REGISTRY.counter("hvd_steady_state_exits").value(
        reason="signature_change") >= 1

    # Re-converge; partial batch then an eviction touching a scheduled
    # bit: the already-submitted request must flush back into the
    # negotiation queue (entry stays in the table).
    for _ in range(3):
        _drive_cycle(rp, ["x.a"], bits={"x.a": (7,)})
    assert rp.active
    # (single-tensor schedule: submit nothing, evict bit 7)
    rp.on_evictions([7])
    assert not rp.active
    assert metrics.REGISTRY.counter("hvd_steady_state_exits").value(
        reason="eviction") >= 1

    # Armed failpoint: next replay submission exits instead.
    for _ in range(3):
        _drive_cycle(rp, ["x.a"])
    assert rp.active
    fp.configure("replay.test=delay(0s,times=0)")
    try:
        assert fp.ENABLED
        assert not rp.replay_submit(_req("x.a"), _entry("x.a"))
        assert not rp.active
        assert metrics.REGISTRY.counter(
            "hvd_steady_state_exits").value(reason="failpoint") >= 1
    finally:
        fp.reset()

    # Frames during replay (a peer negotiated): defensive exit.
    for _ in range(3):
        _drive_cycle(rp, ["x.a"])
    assert rp.active
    rp.on_responses("rs", [(_resp(["other"]), ())])
    assert not rp.active
    assert metrics.REGISTRY.counter("hvd_steady_state_exits").value(
        reason="frame_during_replay") >= 1

    # Disruptions (join/barrier/group/process-set) reset convergence.
    for _ in range(3):
        _drive_cycle(rp, ["x.a"])
    assert rp.active
    rp.note_disruption("join")
    assert not rp.active
    assert metrics.REGISTRY.counter("hvd_steady_state_exits").value(
        reason="join") >= 1


def test_tracker_partial_batch_flush_requeues_requests():
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["p.a", "p.b"]
    # Converge on a FUSED two-tensor batch (one CB batch per cycle).
    for _ in range(3):
        for name in names:
            r = _req(name)
            if not rp.observe_submit(r):
                pass
        if not rp.active:
            rp.on_responses("cb", [(_resp(names), (1, 2))])
    assert rp.active
    # Half-submit the fused batch, then break out via disruption: the
    # pending request must land in the negotiation queue.
    assert rp.replay_submit(_req("p.a"), _entry("p.a"))
    assert not rt.executed  # batch incomplete, nothing ran
    rp.note_disruption("group")
    assert rt.tensor_queue.pending_count() == 1
    assert rt.woken >= 1
    # Its entry is still resolvable for the negotiated response.
    assert rt.tensor_queue.get_entry("p.a") is not None


def test_tracker_never_engages_on_rs_or_changing_cycles():
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    for _ in range(6):                      # full rounds, never CB
        _drive_cycle(rp, ["r.a"], kind="rs")
    assert not rp.active
    for i in range(6):                      # alternating shapes
        r = _req("s.a", shape=(4 + (i % 2),))
        assert not rp.observe_submit(r)
        rp.on_responses("cb", [(_resp(["s.a"]), (i,))])
    assert not rp.active


def test_allgather_cycles_never_stabilize():
    """ALLGATHER dim-0 may legally differ per rank, so replay must
    never freeze a cycle containing one (see replay.py)."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=1)
    ag = Request(request_rank=0, request_type=RequestType.ALLGATHER,
                 tensor_name="g.a", tensor_shape=(4,),
                 tensor_type=DataType.FLOAT32)
    assert not rp.eligible(ag)
    assert rp.eligible(_req("g.b"))


def test_process_set_traffic_never_stabilizes_on_any_rank():
    """Process-set members and non-members see different submission
    streams for the same CB broadcasts — replay must stay off for
    both sides (divergent engagement would deadlock the first global
    tensor after entry)."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=1)
    ps_req = Request(request_rank=0,
                     request_type=RequestType.ALLREDUCE,
                     tensor_name="ps.a", tensor_shape=(4,),
                     tensor_type=DataType.FLOAT32, reduce_op="Sum",
                     process_set_id=1, process_set_ranks=(0, 1))
    assert not rp.eligible(ps_req)          # member side: submit hook
    # Non-member side: the ps CB broadcast dirties the cycle even
    # though this rank never submitted the tensor.
    for _ in range(4):
        rp.observe_submit(_req("ps.glob"))
        ps_resp = _resp(["ps.a"])
        ps_resp.process_set_id = 1
        ps_resp.process_set_ranks = (0, 1)
        rp.on_responses("cb", [(_resp(["ps.glob"]), (1,)),
                               (ps_resp, (2,))])
    assert not rp.active


def test_inactive_eviction_never_touches_tracking_state():
    """An EV frame landing MID-CYCLE (recv-thread timing) must not
    perturb tracking: acting on it would tie state to WHICH cycle was
    current when the recv thread ran — a different cycle per rank —
    and ranks would later freeze rotated/offset schedules (one rank
    silent while a peer negotiates = wedge).  The evicted tensor's
    renegotiation breaks convergence via its RS round instead, which
    is content-deterministic."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["anc.a", "anc.b"]
    _drive_cycle(rp, names)
    # Mid-cycle eviction: first key of the next cycle submitted, then
    # the EV arrives before the rest of the cycle.
    rp.observe_submit(_req(names[0]))
    rp.on_responses("cb", [(_resp([names[0]]), (0,))])
    before = rp.stats()["stable_cycles"]
    rp.on_evictions([99])                    # inactive: no-op
    assert rp.stats()["stable_cycles"] == before
    assert rp._cycle and rp._cycle[0][0] == (0, names[0])
    rp.observe_submit(_req(names[1]))
    rp.on_responses("cb", [(_resp([names[1]]), (1,))])
    # Convergence continues on the SAME anchor; the frozen schedule
    # leads with the original leading key on every rank.
    for _ in range(4):
        _drive_cycle(rp, names)
    assert rp.active
    assert rp._schedule[0].keys[0] == (0, names[0])


def test_untracked_traffic_voids_streak_via_op_index_floor():
    """Process-set / error traffic raises a content-deterministic
    op-index floor instead of flagging the (timing-local) current
    cycle; the floor voids every cycle of the streak that started
    before it — including retroactively at the entry check — so all
    ranks block entry for the same K cycles no matter when their recv
    thread processed the frame."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["flr.a"]
    for _ in range(2):
        _drive_cycle(rp, names)              # streak: stable -> 1
    ps_resp = _resp(["ps.x"])
    ps_resp.process_set_id = 1
    ps_resp.process_set_ranks = (0, 1)
    rp.on_responses("cb", [(ps_resp, (9,))])  # floor = ops so far
    # The next boundary would have shown stable >= warmup without the
    # floor; entry must be refused and the streak restarted.
    _drive_cycle(rp, names)
    _drive_cycle(rp, names)
    assert not rp.active
    # A fresh streak strictly after the floor engages normally.
    for _ in range(3):
        _drive_cycle(rp, names)
    assert rp.active


def test_cross_boundary_async_overlap_disables_permanently():
    """A clean all-CB cycle whose deliveries do not cover its
    submissions proves the program holds async handles ACROSS the
    cycle boundary — convergence would then be a per-rank race, so
    the tracker must lock itself off for good (a boundary-synchronous
    loop can never trip this: the submitter is blocked until
    delivery, and observation precedes delivery)."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["ovl.a", "ovl.b"]
    _drive_cycle(rp, names)
    # Next cycle: second response still in flight when the boundary
    # submission (first key again) arrives.
    rp.observe_submit(_req(names[0]))
    rp.on_responses("cb", [(_resp([names[0]]), (0,))])
    rp.observe_submit(_req(names[1]))        # response never delivered
    assert not rp.observe_submit(_req(names[0]))   # boundary: overlap
    assert not rp.enabled
    assert rp.stats()["disabled_reason"] == "async_overlap"
    # No amount of subsequent clean cycles re-engages.
    for _ in range(6):
        _drive_cycle(rp, names)
    assert not rp.active


def test_duplicate_name_different_signatures_freezes_positionally():
    """A cycle may contain the same (non-leading) tensor name twice
    with different signatures — sequential reuse.  The frozen schedule
    must keep BOTH signatures in submission order; a name-keyed lookup
    would freeze only the last one and churn exit/enter forever on
    'signature_change'."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    stream = [("dup.lead", (4,)), ("dup.x", (4,)), ("dup.x", (16,))]

    def one_cycle():
        entered = False
        for i, (name, shape) in enumerate(stream):
            r = _req(name, shape)
            if rp.active:
                assert rp.replay_submit(r, _entry(name))
                continue
            if rp.observe_submit(r):
                entered = True
                assert rp.replay_submit(r, _entry(name))
                continue
            rp.on_responses("cb", [(_resp([name]), (i,))])
        return entered

    for _ in range(2):
        assert not one_cycle()
    assert one_cycle()     # boundary submission engages
    assert rp.active
    sig_exits = metrics.REGISTRY.counter(
        "hvd_steady_state_exits").value(reason="signature_change")
    n = len(rt.executed)
    one_cycle()            # full cycle from the frozen schedule
    assert rp.active, "replay churned out on a duplicate-name cycle"
    assert len(rt.executed) == n + len(stream)
    assert metrics.REGISTRY.counter(
        "hvd_steady_state_exits").value(
            reason="signature_change") == sig_exits


def test_armed_failpoint_gates_entry_not_just_exit():
    """With failpoints armed, the tracker must never ENTER replay —
    otherwise a chaos run oscillates enter/exit every warmup-K cycles,
    inflating hvd_steady_state_entries/exits forever.  Disarming
    lets the (still-converged) stream engage at the next boundary."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    fp.configure("some.site=delay(0s,times=0)")
    try:
        for _ in range(6):
            assert not _drive_cycle(rp, ["fpg.a"])
            assert not rp.active
    finally:
        fp.reset()
    _drive_cycle(rp, ["fpg.a"])
    assert rp.active


def test_never_closing_cycle_memory_stays_bounded(monkeypatch):
    """Auto-named tensors (every eager op unnamed) never repeat a
    leading key, so the cycle never closes: past MAX_CYCLE_OPS the
    tracker must void and re-anchor instead of accumulating tracking
    state for the process lifetime."""
    from horovod_tpu.common import replay as replay_mod
    monkeypatch.setattr(replay_mod, "MAX_CYCLE_OPS", 8)
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    for i in range(50):
        r = _req("ar.noname.%d" % i)
        assert not rp.observe_submit(r)
        rp.on_responses("cb", [(_resp([r.tensor_name]), (i % 32,))])
        assert len(rp._cycle) <= 8
        assert len(rp._delivered) <= 8
    assert not rp.active


def test_joined_rank_accumulates_no_delivery_history():
    """A joined rank keeps receiving every CB broadcast (it
    participates with zeros) but never submits, so no cycle boundary
    ever drains the tracker — delivery history must not grow."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    _drive_cycle(rp, ["j.a"])
    rp.note_disruption("join")
    for i in range(1000):
        rp.on_responses("cb", [(_resp(["j.a"]), (i % 7,))])
    assert len(rp._delivered) == 0


# ---------------------------------------------------------------------------
# end to end: real worker processes, every op checked for correctness
# ---------------------------------------------------------------------------

def test_replay_engages_and_every_exit_matches_negotiated_results():
    """2 real ranks: replay engages after warm-up; unseen-tensor,
    failpoint, barrier and join exits all fall back to negotiation;
    every allreduce along the way (replayed or negotiated) must equal
    the closed-form expectation — results bit-identical either way
    (integral float32 values, so equality is exact)."""
    body = """
from horovod_tpu.common import metrics as _m, basics
from horovod_tpu.common import failpoints as _fp
rt = basics._state().runtime
assert rt.replay is not None
c = _m.REGISTRY.counter
buf = np.full((33,), float(RANK + 1), np.float32)
expect = float(sum(range(1, SIZE + 1)))

def loop(name, n, scale=1.0):
    for _ in range(n):
        out = np.asarray(hvd.allreduce(buf * scale, op=hvd.Sum,
                                       name=name))
        assert (out == expect * scale).all(), (name, out[0])

# Phase 1: converge + engage + replay.
loop("rp.t0", 12)
assert c("hvd_steady_state_entries").value() >= 1
assert rt.replay.stats()["active"]
assert c("hvd_steady_state_cycles_replayed").value() >= 1

# The zero-wire count: a replayed window puts no CH and no RQ frame
# on the uplink.  Bounded retries, as in tests/test_tune.py: a
# transient exit on a loaded machine legally negotiates a few cycles.
for attempt in range(4):
    while not rt.replay.stats()["active"]:
        loop("rp.t0", 1)
    f0 = dict(rt.controller.stats)
    loop("rp.t0", 12)
    f1 = dict(rt.controller.stats)
    frames = sum(f1[k] - f0[k] for k in ("rq_frames", "ch_frames"))
    if frames == 0:
        break
assert frames == 0, ("uplink frames during the replay window", frames)

# Phase 2: unseen tensor exits; both names then stay correct.
loop("rp.t1", 2, scale=2.0)
assert c("hvd_steady_state_exits").value(reason="unseen_tensor") >= 1

# Phase 3: re-engage on the two-tensor cycle, then an armed failpoint
# exits and pins the negotiated path while armed.
for _ in range(6):
    loop("rp.t0", 1)
    loop("rp.t1", 1, scale=2.0)
_fp.configure("replay.e2e=delay(0s,times=0)")
try:
    loop("rp.t0", 1)
    loop("rp.t1", 1, scale=2.0)
    assert c("hvd_steady_state_exits").value(reason="failpoint") >= 1
    assert not rt.replay.stats()["active"]
finally:
    _fp.reset()

# Phase 4: re-engage, then a barrier WHILE ACTIVE exits replay with
# ITS label — the barrier request must route to note_disruption, not
# get matched against the frozen schedule as an "unseen tensor" —
# and never breaks correctness.
for _ in range(6):
    loop("rp.t0", 1)
    loop("rp.t1", 1, scale=2.0)
assert rt.replay.stats()["active"]
hvd.barrier()
assert c("hvd_steady_state_exits").value(reason="barrier") >= 1
loop("rp.t0", 6)

# Phase 5: join exits replay (reason=join) and completes.
assert rt.replay.stats()["active"]
hvd.join()
assert c("hvd_steady_state_exits").value(reason="join") >= 1
loop("rp.t0", 2)

# HOROVOD_LOCKWITNESS=1 armed the lock-order witness at init: the
# whole negotiate/replay/exit lifecycle above ran under it.  Any
# ABBA ordering between the runtime/controller/replay locks fails
# here with both sites named (docs/static_analysis.md).
from horovod_tpu.common import lockwitness as lw
assert lw.ENABLED and lw.edge_count() > 0, "witness never engaged"
lw.assert_no_cycles()
print("REPLAY_E2E_OK", RANK)
hvd.shutdown()
"""
    results = run_workers(
        body, nproc=2, timeout=180,
        extra_env={"HOROVOD_STEADY_STATE_REPLAY": "1",
                   "HOROVOD_LOCKWITNESS": "1"})
    assert_all_ok(results)
    for _, out in results:
        assert "REPLAY_E2E_OK" in out


def test_replay_disabled_by_env_knob():
    body = """
from horovod_tpu.common import basics
rt = basics._state().runtime
assert rt.replay is None, "HOROVOD_STEADY_STATE_REPLAY=0 ignored"
buf = np.full((9,), float(RANK + 1), np.float32)
for _ in range(8):
    out = np.asarray(hvd.allreduce(buf, op=hvd.Sum, name="off.t0"))
    assert out[0] == sum(range(1, SIZE + 1))
hvd.shutdown()
"""
    assert_all_ok(run_workers(
        body, nproc=2, timeout=120,
        extra_env={"HOROVOD_STEADY_STATE_REPLAY": "0"}))


def test_eviction_churn_under_tiny_cache_stays_correct():
    """Coordinator cache capacity 1 with two live tensors: constant
    evict/renegotiate churn (EV frames) — replay must never freeze a
    wrong schedule and every result must stay exact."""
    body = """
from horovod_tpu.common import basics
buf = np.full((17,), float(RANK + 1), np.float32)
expect = float(sum(range(1, SIZE + 1)))
for i in range(10):
    for name, scale in (("ev.a", 1.0), ("ev.b", 3.0)):
        out = np.asarray(hvd.allreduce(buf * scale, op=hvd.Sum,
                                       name=name))
        assert (out == expect * scale).all(), (i, name, out[0])
stats = basics._state().runtime.controller.stats
assert stats["ev_frames"] > 0, "no eviction churn generated"
print("EVICT_OK", RANK)
hvd.shutdown()
"""
    results = run_workers(body, nproc=2, timeout=120,
                          extra_env={"HOROVOD_CACHE_CAPACITY": "1",
                                     "HOROVOD_STEADY_STATE_REPLAY":
                                         "1"})
    assert_all_ok(results)
    for _, out in results:
        assert "EVICT_OK" in out


# ---------------------------------------------------------------------------
# coalesced-frame protocol at 8 ranks (both coordinators)
# ---------------------------------------------------------------------------

NPROC = 8


def _coordinators():
    from horovod_tpu.common.controller_net import CoordinatorServer
    yield "python", lambda: CoordinatorServer(
        NPROC, port=0, fusion_threshold=1 << 20,
        stall_warning_time_s=60.0)
    try:
        from horovod_tpu.native import NativeCoordinatorServer, available
        if available():
            yield "native", lambda: NativeCoordinatorServer(
                NPROC, port=0, fusion_threshold=1 << 20)
    except Exception:
        pass


def _connect_ranks(srv, n=NPROC):
    from horovod_tpu.common.controller_net import _send_frame
    conns = []
    for rank in range(n):
        c = socket.create_connection(("127.0.0.1", srv.port))
        c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # Registration is an RQ frame (frame-parity: the coordinator
        # refuses any other first kind since hvdlint mechanized the
        # rule — it used to guess a rank out of arbitrary bytes).
        _send_frame(c, b"RQ", struct.pack("<i", rank))
        conns.append(c)
    deadline = time.monotonic() + 10
    while srv.departure_counts()[0] < n and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    assert srv.departure_counts()[0] == n
    return conns


def _recv(conn, timeout=10.0):
    from horovod_tpu.common.controller_net import _recv_frame
    conn.settimeout(timeout)
    frame = _recv_frame(conn)
    assert frame is not None, "peer closed before a frame arrived"
    return frame


# ---------------------------------------------------------------------------
# alltoall exclusion (the sparse/DLRM traffic pattern)
# ---------------------------------------------------------------------------

def test_alltoall_request_never_eligible_and_resets_tracking():
    """Submit-side: alltoall is structurally non-replayable (splits
    legally vary per step); a cycle containing one never stabilizes."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    a2a = Request(request_rank=0, request_type=RequestType.ALLTOALL,
                  tensor_name="sp.ids", tensor_shape=(5,),
                  tensor_type=DataType.FLOAT32, splits=(2, 3))
    assert not rp.eligible(a2a)
    # Every cycle: one allreduce + one alltoall (as runtime.submit
    # routes it: note_disruption with the request-type label).
    for _ in range(8):
        rp.observe_submit(_req("sp.dense"))
        rp.on_responses("cb", [(_resp(["sp.dense"]), (0,))])
        rp.note_disruption("alltoall")
    assert not rp.active
    assert rp.stats()["stable_cycles"] == 0


def test_alltoall_frame_during_replay_exits_with_own_label():
    """Delivery-side: an ALLTOALL response frame arriving while a rank
    replays must exit with reason=alltoall (its own label), not the
    generic frame_during_replay — the sparse workload's exits must be
    attributable in hvd_steady_state_exits."""
    rt = _FakeRuntime()
    rp = SteadyStateReplay(rt, warmup_cycles=2)
    names = ["a2f.x"]
    for _ in range(3):
        _drive_cycle(rp, names)
    assert rp.active
    c = metrics.REGISTRY.counter("hvd_steady_state_exits")
    before = c.value(reason="alltoall")
    a2a = Response(response_type=ResponseType.ALLTOALL,
                   tensor_names=["sp.ids"],
                   tensor_type=DataType.FLOAT32,
                   tensor_sizes=[1, 1], tensor_shapes=[(2,)])
    rp.on_responses("cb", [(a2a, ())])
    assert not rp.active
    assert c.value(reason="alltoall") == before + 1
    # A non-alltoall frame keeps the generic label.
    for _ in range(4):
        _drive_cycle(rp, names)
    assert rp.active
    g0 = c.value(reason="frame_during_replay")
    rp.on_responses("cb", [(_resp(["a2f.x"]), (0,))])
    assert c.value(reason="frame_during_replay") == g0 + 1


def test_alltoall_excluded_from_replay_at_8_ranks():
    """8 real ranks: replay engages on a dense cycle; an alltoall
    (uneven, per-rank-varying splits — the sharded-embedding exchange
    shape) exits with reason=alltoall; cycles that keep containing
    alltoall NEVER re-freeze; dropping it re-engages.  Results exact
    throughout."""
    body = """
from horovod_tpu.common import metrics as _m, basics
rt = basics._state().runtime
assert rt.replay is not None
c = _m.REGISTRY.counter
buf = np.full((17,), float(RANK + 1), np.float32)
expect = float(sum(range(1, SIZE + 1)))

def dense(n):
    for _ in range(n):
        out = np.asarray(hvd.allreduce(buf, op=hvd.Sum, name="xa.t0"))
        assert (out == expect).all(), out[0]

def a2a(tag):
    # rank R sends 1 or 2 rows to each dest: splits vary per rank.
    splits = np.array([1 + (RANK + d) % 2 for d in range(SIZE)])
    x = np.arange(splits.sum(), dtype=np.float32) + 1000.0 * RANK
    y, recv = hvd.alltoall(x, splits=splits, name="xa.a2a." + tag)
    exp_recv = [1 + (s + RANK) % 2 for s in range(SIZE)]
    np.testing.assert_array_equal(np.asarray(recv), exp_recv)
    assert np.asarray(y).shape[0] == sum(exp_recv)

# Engage on the dense cycle.
dense(12)
assert rt.replay.stats()["active"]
entries_before = c("hvd_steady_state_entries").value()

# Submit-side exit while ACTIVE: alltoall carries its own label.
a2a("first")
assert c("hvd_steady_state_exits").value(reason="alltoall") >= 1
assert not rt.replay.stats()["active"]

# Cycles that contain an alltoall must never freeze again.
for i in range(8):
    dense(1)
    a2a("loop%d" % i)
assert not rt.replay.stats()["active"]
assert c("hvd_steady_state_entries").value() == entries_before

# Drop the alltoall: the dense cycle re-engages (the exclusion was
# the alltoall, not collateral damage).
dense(12)
assert rt.replay.stats()["active"]
assert c("hvd_steady_state_entries").value() > entries_before
print("A2A_EXCLUSION_OK", RANK)
hvd.shutdown()
"""
    results = run_workers(
        body, nproc=8, timeout=240,
        extra_env={"HOROVOD_STEADY_STATE_REPLAY": "1"})
    assert_all_ok(results)
    for _, out in results:
        assert "A2A_EXCLUSION_OK" in out


@pytest.mark.parametrize("kind", [k for k, _ in _coordinators()])
def test_coalesced_frames_fuse_whole_cycles_at_8_ranks(kind):
    """One RQ frame carrying a whole 4-tensor cycle per rank must come
    back as ONE RS broadcast whose responses fuse the cycle (frame
    count tracks batches, not tensors); the coalesced CH round then
    answers with ONE CB frame batching all 4 bits."""
    from horovod_tpu.common.controller_net import _send_frame
    make = dict(_coordinators())[kind]
    srv = make()
    conns = []
    names = ["co.%d" % i for i in range(4)]
    try:
        conns = _connect_ranks(srv)
        for rank, conn in enumerate(conns):
            reqs = [Request(request_rank=rank,
                            request_type=RequestType.ALLREDUCE,
                            tensor_name=n, tensor_shape=(64,),
                            tensor_type=DataType.FLOAT32,
                            reduce_op="Sum") for n in names]
            _send_frame(conn, b"RQ", pack_request_list(reqs))
        bits = {}
        for conn in conns:
            magic, payload = _recv(conn)
            assert magic == b"RS", magic
            responses, _ = unpack_response_list(payload)
            # The whole cycle completed in one broadcast; same-dtype
            # allreduces fuse into ONE response covering all 4.
            got = [n for r in responses for n in r.tensor_names]
            assert sorted(got) == sorted(names)
            assert len(responses) == 1, \
                "cycle did not fuse: %d responses" % len(responses)
            for r in responses:
                assert not r.error_message
                for n, b in zip(r.tensor_names, r.cache_bits):
                    assert b >= 0
                    bits.setdefault(n, b)
        # Steady state: ONE CH frame with all 4 bits per rank -> ONE
        # CB frame with one 4-bit batch.
        for conn in conns:
            _send_frame(conn, b"CH",
                        pack_bits([bits[n] for n in names]))
        for conn in conns:
            magic, payload = _recv(conn)
            assert magic == b"CB", magic
            batches = unpack_bit_batches(payload)
            assert len(batches) == 1
            assert sorted(batches[0]) == sorted(bits.values())
    finally:
        for c in conns:
            c.close()
        srv.stop()
