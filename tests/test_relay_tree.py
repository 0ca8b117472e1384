"""Relay-tree control plane: topology, depth-aware deadlines, the
O(due) liveness sweep, transparent relay failover, and the scale
probe (docs/architecture.md tree section, docs/failure_recovery.md
re-homing state machine).

Tier-1 keeps the deterministic seconds-scale drills (the
test_chaos_smoke precedent): an 8-rank fanout-2 world through real
relays, one relay killed mid-negotiation, bit-identical completion in
well under 10 s.  The 64/256-rank matrix rides the `slow` marker.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

from chaos_soak import (ChaosWorld, run_negotiation_scale_probe,  # noqa: E402
                        run_relay_drill, run_relay_matrix,
                        run_scale_lane)

from horovod_tpu.common import env as env_mod  # noqa: E402
from horovod_tpu.common import metrics as hm  # noqa: E402
from horovod_tpu.common import relay as relay_mod  # noqa: E402


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

def test_plan_covers_every_rank_exactly_once():
    for size, fanout in ((8, 2), (64, 8), (256, 8), (17, 3)):
        plan = relay_mod.plan_tree(size, fanout)
        covered = {}
        for r in range(1, size):
            rid = plan.leaf_parent(r)
            assert rid is not None, (size, fanout, r)
            covered.setdefault(rid, []).append(r)
            info = plan.relays[rid]
            assert info.level == 0
            assert info.leaf_lo <= r < info.leaf_hi
        # Rank 0 is ALWAYS a direct root link (it hosts the
        # coordinator; a relay hop would buy nothing).
        assert plan.leaf_parent(0) is None
        assert plan.ancestors_of_leaf(0) == []
        for rid, leaves in covered.items():
            assert len(leaves) <= fanout, (size, fanout, rid)


def test_plan_parent_chains_reach_root_with_bounded_arity():
    plan = relay_mod.plan_tree(256, 8)
    assert len(plan.root_relays) + 1 <= 8 + 1  # root links O(fanout)
    for rid, info in plan.relays.items():
        chain = plan.relay_ancestors(rid)
        # Chains terminate (no cycles) and end at a root relay.
        assert len(chain) <= plan.levels
        if chain:
            assert chain[-1] in plan.root_relays
        assert len(info.child_relays) <= 8
    # Every leaf's hop count equals the level count of its chain.
    for r in (1, 100, 255):
        assert plan.leaf_hops(r) == len(plan.ancestors_of_leaf(r))
        assert plan.leaf_hops(r) == plan.levels


def test_plan_trivial_cases_stay_flat():
    assert relay_mod.plan_tree(8, 0) is None      # knob off
    assert relay_mod.plan_tree(9, 8) is None      # fits the root
    assert relay_mod.plan_tree(2, 1) is None
    assert relay_mod.plan_tree(10, 8) is not None  # 9 leaves > 8


def test_plan_host_assignment_deterministic():
    plan = relay_mod.plan_tree(64, 8)
    hosts = {rid: plan.relays[rid].host_rank
             for rid in plan.relays}
    # Level-0 relay k serves [1+8k, 1+8(k+1)) and is hosted by its
    # lowest leaf.
    assert hosts[0] == 1
    # relays_hosted_by returns highest level first (parents must come
    # up before children connect).
    for rank in (1, 9, 17):
        mine = plan.relays_hosted_by(rank)
        levels = [plan.relays[rid].level for rid in mine]
        assert levels == sorted(levels, reverse=True)


# ---------------------------------------------------------------------------
# knobs + the depth-aware deadline formula
# ---------------------------------------------------------------------------

def test_coord_fanout_knob_parsing(monkeypatch):
    from horovod_tpu.common.env import Knobs
    monkeypatch.delenv("HOROVOD_COORD_FANOUT", raising=False)
    assert Knobs.from_env().coord_fanout == 0          # flat default
    monkeypatch.setenv("HOROVOD_COORD_FANOUT", "8")
    assert Knobs.from_env().coord_fanout == 8
    monkeypatch.setenv("HOROVOD_COORD_FANOUT", "-3")
    assert Knobs.from_env().coord_fanout == 0          # clamped
    monkeypatch.setenv("HOROVOD_COORD_FANOUT", "bogus")
    assert Knobs.from_env().coord_fanout == 0


def test_depth_aware_liveness_timeout_formula():
    base = 2.0
    # hops=0 is the flat-star deadline, exactly.
    assert env_mod.depth_aware_liveness_timeout(base, 0) == base
    # Documented formula: base * (1 + HOP_SLACK * hops).
    for hops in (1, 2, 5):
        assert env_mod.depth_aware_liveness_timeout(base, hops) == \
            pytest.approx(base * (1 + env_mod.LIVENESS_HOP_SLACK *
                                  hops))
    # Monotone in depth; negative hops clamp to the flat deadline.
    assert env_mod.depth_aware_liveness_timeout(base, -1) == base


def test_relay_addr_map_parsing(monkeypatch):
    monkeypatch.delenv("HOROVOD_RELAY_ADDRS", raising=False)
    assert relay_mod.relay_addr_map() == {}
    monkeypatch.setenv("HOROVOD_RELAY_ADDRS",
                       json.dumps({"0": "127.0.0.1:1234",
                                   "3": "10.0.0.1:9"}))
    assert relay_mod.relay_addr_map() == {0: "127.0.0.1:1234",
                                          3: "10.0.0.1:9"}
    monkeypatch.setenv("HOROVOD_RELAY_ADDRS", "not json")
    assert relay_mod.relay_addr_map() == {}


# ---------------------------------------------------------------------------
# deadline heap: the O(due) sweep perf pin (PR 6 one-attribute-check
# precedent: the satellite's cost claim is asserted, not assumed)
# ---------------------------------------------------------------------------

def test_deadline_heap_sweep_visits_only_due_links():
    heap = relay_mod.DeadlineHeap()
    now = 1000.0
    timeout = 5.0
    heard = {k: now for k in range(1000)}

    def deadline_fn(k):
        t = heard.get(k)
        return None if t is None else t + timeout

    for k in range(1000):
        heap.schedule(k, heard[k] + timeout)
    # Sweep while nothing is due: ZERO entries visited — the sweep
    # cost does not scale with the idle population.
    v0 = heap.visits
    assert heap.due(now + 1.0, deadline_fn) == []
    assert heap.visits == v0
    # All links refresh (traffic): one lazy re-push each when their
    # RECORDED deadline lapses, then quiet again.
    for k in heard:
        heard[k] = now + 6.0
    assert heap.due(now + timeout + 0.1, deadline_fn) == []
    assert heap.visits == v0 + 1000   # one amortized visit per window
    v1 = heap.visits
    assert heap.due(now + timeout + 1.0, deadline_fn) == []
    assert heap.visits == v1          # re-pushed at true deadlines
    # One link goes silent (its last-heard stays at now+6 while every
    # other refreshes): exactly it is yielded at the next window.
    for k in heard:
        if k != 7:
            heard[k] = now + 20.0
    due = heap.due(now + 12.0, deadline_fn)
    assert due == [7]
    # Dropped links (deadline_fn -> None) vanish from the heap.
    del heard[8]
    heap.due(now + 100.0, deadline_fn)
    assert 8 not in [k for _, _, k in heap._heap]
    # Deadline ties across heterogeneous key types (ints, tuples,
    # tokens) must never compare the keys: the seq field breaks them.
    heap.schedule(("relay", 1), now + 200.0)
    heap.schedule(3, now + 200.0)
    heap.schedule(("relay", 0), now + 200.0)
    assert heap.due(now + 300.0, lambda k: None) == []


def test_rb_rd_frame_packing_roundtrip():
    items = [(3, 7, b"RQ", b"payload-a"), (255, 1, b"CH", b""),
             (0, 2, b"RG", b"\x00\x01\x02")]
    assert relay_mod.unpack_rb_items(
        relay_mod.pack_rb_items(items)) == items
    target, magic, payload = relay_mod.unpack_rd(
        relay_mod.pack_rd(42, b"WE", b"hello"))
    assert (target, magic, payload) == (42, b"WE", b"hello")


# ---------------------------------------------------------------------------
# e2e: the tree carries real negotiation, O(fanout) links at the root
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_tree_world_collectives_bit_correct_and_root_links_bounded():
    """8 ranks, fanout 2: every collective reduces bit-correctly
    through two relay levels, and the root holds O(fanout) links —
    one direct leaf (rank 0) plus the top relays — while every other
    rank is relay-attached."""
    world = ChaosWorld(8, stall_shutdown_s=6.0, fanout=2,
                       liveness_interval_s=0.3,
                       reconnect_grace_s=1.2)
    try:
        outs = {}
        for step in range(3):
            ts = []
            for r in range(8):
                def go(r=r, step=step):
                    outs[(r, step)] = world.collective(
                        r, "allreduce", "tree.%d" % (step % 2),
                        np.full((17,), r + 1.0, np.float32), step,
                        20.0)
                t = threading.Thread(target=go, daemon=True)
                t.start()
                ts.append(t)
            for t in ts:
                t.join(timeout=25)
        expected = np.full((17,), sum(r + 1.0 for r in range(8)),
                           np.float32)
        assert len(outs) == 24
        for key, out in outs.items():
            np.testing.assert_array_equal(out, expected, err_msg=str(key))
        srv = world.runtimes[0].controller.server
        assert sorted(srv._conns.keys()) == [0]
        assert len(srv._relay_conns) == len(world.plan.root_relays)
        assert sorted(srv._rank_via.keys()) == list(range(1, 8))
        # Uplink batching engaged: the root consumed RB frames.
        rb = hm.REGISTRY.counter("hvd_frames_recv_total")
        assert rb.value(kind="RB") > 0
    finally:
        world.close()


@pytest.mark.chaos
def test_relay_failover_smoke_8_ranks():
    """TIER-1 relay failover: kill one relay mid-negotiation in an
    8-rank fanout-2 world.  The subtree re-homes through its ancestor
    chain; the world NEVER breaks: zero fatal unwinds, zero hangs,
    every collective bit-correct, re-home inside the depth-aware
    bound — all in a few seconds."""
    t0 = time.monotonic()
    rec = run_relay_drill(fault="kill", when="negotiation", ranks=8,
                          fanout=2, seed=3)
    assert rec["ok"], {k: rec.get(k) for k in
                       ("hangs", "errors", "results_bad",
                        "fatal_events", "rehomed", "rehome_s")}
    assert rec["fatal_events"] == []
    assert rec["rehomed"] >= len(rec["subtree"])
    assert rec["rehome_s"] <= rec["rehome_bound_s"]
    # Postmortem (flight recorder + blackbox_merge): the per-rank
    # dumps alone must merge into a VALID chrome trace whose verdict
    # names the relay the drill actually killed.
    pm = rec["postmortem"]
    assert pm["ok"], pm
    assert pm["failed_relay"] == rec["victim_relay"]
    assert pm["trace_errors"] == []
    assert pm["dumps"] >= 8  # every thread-rank dumped its own file
    assert time.monotonic() - t0 < 12.0


@pytest.mark.chaos
def test_relay_wedge_transparent_8_ranks():
    """A SIGSTOP-wedged relay (sockets open, nothing flows): leaves
    behind it must self-detect via the depth-aware deadline and
    re-home without the world breaking."""
    rec = run_relay_drill(fault="wedge", when="idle", ranks=8,
                          fanout=2, seed=5)
    assert rec["ok"], {k: rec.get(k) for k in
                       ("hangs", "errors", "results_bad",
                        "fatal_events", "rehomed", "rehome_s")}
    assert rec["fatal_events"] == []


@pytest.mark.chaos
def test_tree_metrics_aggregation_covers_all_ranks():
    """MQ/MR satellite: relays pre-aggregate their subtree's MR
    replies into MA frames, so the root's merged view covers every
    rank while its own recv path only saw O(fanout) aggregate
    frames."""
    world = ChaosWorld(8, stall_shutdown_s=6.0, fanout=2)
    try:
        srv = world.runtimes[0].controller.server
        deadline = time.monotonic() + 12.0
        merged = None
        while time.monotonic() < deadline:
            srv.request_metrics()
            time.sleep(0.25)
            merged = srv.merged_metrics()
            if merged is not None and \
                    merged.get("ranks") == list(range(8)):
                break
        assert merged is not None, "no metrics ever merged"
        assert merged["ranks"] == list(range(8)), merged["ranks"]
        # The aggregation really rode MA frames (not 8 direct MRs).
        agg = hm.REGISTRY.counter("hvd_relay_agg_metrics_total")
        assert agg.value() > 0
    finally:
        world.close()


@pytest.mark.chaos
def test_straggler_attribution_rides_ma_aggregation():
    """Relay-tree straggler satellite: with replay engaged at
    fanout=2 the coordinator's negotiation view is dark AND most
    ranks' MR replies are consumed by their relays — the scorer must
    still name the failpoint-delayed rank from the per-rank phase
    summaries carried through MR→MA pre-aggregation (per-rank labels
    survive the snapshot merge; the root never sees one blended
    number per subtree)."""
    from chaos_soak import run_straggler_drill

    agg = hm.REGISTRY.counter("hvd_relay_agg_metrics_total")
    agg0 = agg.value()
    rec = run_straggler_drill(mode="replay", ranks=8, victim=5,
                              delay_ms=25.0, seed=2, fanout=2)
    assert rec["ok"], {k: rec.get(k) for k in
                       ("named", "tta_s", "victim_score", "replay",
                        "scores", "hangs", "errors")}
    # The per-rank data really rode MA frames (relays pre-aggregated).
    assert agg.value() > agg0


def test_flat_star_still_selectable(monkeypatch):
    """HOROVOD_COORD_FANOUT=0 (the default) keeps the flat star: no
    plan, no relays, no mux — the pre-tree thread-per-link paths."""
    world = ChaosWorld(3, stall_shutdown_s=6.0, fanout=0)
    try:
        assert world.plan is None
        assert world.relays == {}
        # The server may be the native C++ coordinator here (fanout 0
        # does not pin the Python one — that's the point); a Python
        # server must carry no plan and no mux.
        srv = world.runtimes[0].controller.server
        assert getattr(srv, "_plan", None) is None
        assert getattr(srv, "_mux", None) is None
        ctrl = world.runtimes[1].controller
        assert ctrl._addr_chain == [ctrl._addr]
        out = {}

        def go(r):
            out[r] = world.collective(
                r, "allreduce", "flat.x",
                np.full((5,), r + 1.0, np.float32), 0, 15.0)
        ts = [threading.Thread(target=go, args=(r,), daemon=True)
              for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=20)
        np.testing.assert_allclose(out[0], np.full((5,), 6.0))
    finally:
        world.close()


def test_strict_native_rejects_fanout(monkeypatch):
    """HOROVOD_TPU_NATIVE=1 + a relay tree is a config error, not a
    silent demotion (the native coordinator has no relay frames)."""
    from chaos_soak import _StateStub, _free_port, soak_knobs
    from horovod_tpu.common.controller_net import NetworkController
    monkeypatch.setenv("HOROVOD_TPU_NATIVE", "1")
    monkeypatch.setenv("HOROVOD_CONTROLLER_ADDR",
                       "127.0.0.1:%d" % _free_port())
    monkeypatch.delenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", raising=False)
    st = _StateStub(0, 4, soak_knobs(0.0, coord_fanout=2))
    with pytest.raises(RuntimeError, match="HOROVOD_COORD_FANOUT"):
        NetworkController(st)


# ---------------------------------------------------------------------------
# scale probe (protocol-only clients; run_scale_lane sweeps it)
# ---------------------------------------------------------------------------

def test_negotiation_scale_probe_shape_and_fanout_bound():
    tree = run_negotiation_scale_probe(16, 4, rounds=3)
    flat = run_negotiation_scale_probe(16, 0, rounds=3)
    # Deterministic sub-linearity witness: the root sends once per
    # LINK, and the tree bounds links to O(fanout) + rank 0.
    assert flat["root_sends_per_round"] == 16
    assert tree["root_sends_per_round"] == \
        tree["topology"]["root_links"]
    assert tree["root_sends_per_round"] < flat["root_sends_per_round"]
    assert tree["wall_ms"]["median"] > 0
    assert tree["root_broadcast_ms"] >= 0


# ---------------------------------------------------------------------------
# the full matrix + the 64/256-rank lanes (slow)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_relay_matrix_full_8_ranks():
    report = run_relay_matrix(ranks=8, fanout=2, seed=13)
    assert report["ok"], [
        {k: c.get(k) for k in ("kind", "fault", "when", "ok",
                               "victim_kind", "errors")}
        for c in report["cells"] if not c.get("ok")]
    assert len(report["cells"]) == 18


@pytest.mark.chaos
@pytest.mark.slow
def test_relay_kill_drill_64_ranks():
    """The acceptance lane: killing a relay mid-negotiation at 64
    in-process ranks recovers with zero hangs, bit-correct results,
    and detect+re-home inside the depth-aware liveness bound."""
    rec = run_relay_drill(fault="kill", when="negotiation", ranks=64,
                          fanout=8, seed=0)
    assert rec["ok"], {k: rec.get(k) for k in
                       ("hangs", "errors", "results_bad",
                        "fatal_events", "rehomed", "rehome_s",
                        "rehome_bound_s")}
    assert rec["rehomed"] >= len(rec["subtree"]) == 8
    assert rec["rehome_s"] <= rec["rehome_bound_s"]


@pytest.mark.slow
def test_scale_lane_sublinear_to_256():
    out = run_scale_lane(sizes=(8, 64, 256), fanout=8, rounds=5)
    assert out["sublinear"], out
    tree_sends, flat_sends = out["root_sends_tree_vs_flat_at_max"]
    assert tree_sends < flat_sends / 8
