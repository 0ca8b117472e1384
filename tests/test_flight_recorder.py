"""Black-box flight recorder: ring semantics, dump triggers, the
cross-rank causal merge (tools/blackbox_merge.py), the /blackbox
endpoint's auth, and the one-attribute-check perf pin.

The end-to-end postmortem assertions (8-rank drills whose verdicts
must name the actually-killed rank/relay) ride the existing drill
tests — tests/test_liveness.py and tests/test_relay_tree.py — whose
records now embed ``postmortem``; this file covers the recorder and
merge mechanics directly."""

import importlib.util
import json
import os
import signal
import sys
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from horovod_tpu.common import flight_recorder as fr  # noqa: E402


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


blackbox_merge = _load_tool("blackbox_merge")
validate_trace = _load_tool("validate_trace")


@pytest.fixture(autouse=True)
def _clean_recorder():
    fr.reset()
    yield
    fr.reset()


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_bounds_and_eviction():
    """The ring is a fixed-size deque: capacity N holds exactly the
    NEWEST N events; the oldest evict in O(1)."""
    fr.configure(capacity=16, enabled=True)
    for i in range(50):
        fr.record(fr.SUBMIT, rank=0, name="t%d" % i, type="ALLREDUCE")
    evs = fr.events()
    assert len(evs) == 16
    names = [e[4]["name"] for e in evs]
    assert names == ["t%d" % i for i in range(34, 50)]


def test_capacity_floor_and_reconfigure_preserves_tail():
    fr.configure(capacity=4, enabled=True)  # clamped to the floor (16)
    for i in range(20):
        fr.record(fr.NOTE, rank=0, i=i)
    assert len(fr.events()) == 16


def test_typed_event_roundtrip(tmp_path):
    """Events survive dump -> JSON -> reload with kinds, rank tags,
    both clocks, and every payload field intact — and the reserved
    keys (kind/rank) always win over payload fields."""
    fr.configure(capacity=64, enabled=True)
    fr.record(fr.FRAME_TX, rank=3, role="worker", frame="CH",
              nbytes=42, seq=7, sess="abcd1234")
    fr.record(fr.REPLAY, rank=3, phase="exit", reason="alltoall")
    fr.record(fr.CKPT, rank=3, phase="commit", step=12,
              outcome="committed")
    fr.record(fr.PROMOTE, rank=0, role="coord", peer=3, clean=False,
              reason="liveness timeout")
    paths = fr.dump("unit", directory=str(tmp_path))
    assert len(paths) == 2  # rank 0 and rank 3
    by_rank = {}
    for p in paths:
        with open(p) as f:
            d = json.load(f)
        assert d["version"] == 1
        assert d["reason"] == "unit"
        by_rank[d["rank"]] = d
    r3 = by_rank[3]["events"]
    assert [e["kind"] for e in r3] == ["frame_tx", "replay", "ckpt"]
    tx = r3[0]
    assert tx["frame"] == "CH" and tx["nbytes"] == 42 and \
        tx["seq"] == 7 and tx["sess"] == "abcd1234"
    assert tx["rank"] == 3 and tx["mono"] > 0 and tx["wall"] > 0
    assert r3[1]["reason"] == "alltoall"
    assert r3[2]["step"] == 12
    p0 = by_rank[0]["events"][0]
    assert p0["kind"] == "promote" and p0["peer"] == 3 and \
        not p0["clean"]


def test_recent_for_tensors_filters_and_bounds():
    fr.configure(capacity=256, enabled=True)
    for i in range(30):
        fr.record(fr.SUBMIT, rank=1, name="grad/w", type="ALLREDUCE")
        fr.record(fr.SUBMIT, rank=1, name="other", type="ALLREDUCE")
    out = fr.recent_for_tensors(["grad/w"], n=5)
    assert len(out) == 5
    assert all(e["name"] == "grad/w" for e in out)
    assert fr.recent_for_tensors(["nope"]) == []


def test_disabled_records_nothing_and_dump_needs_dir(tmp_path):
    assert not fr.ENABLED
    # note() is gated internally: a disarmed recorder takes no
    # markers (a stale drill.fault would anchor a later postmortem).
    fr.note("drill.fault", victim=3)
    assert fr.events() == []
    # Sites gate on ENABLED, so nothing below should ever run in
    # production; even called directly, dump without a dir is a no-op.
    fr.record(fr.NOTE, rank=0)
    assert fr.dump("x") == []  # no directory configured
    fr.configure(directory=str(tmp_path), capacity=64, enabled=True)
    fr.record(fr.NOTE, rank=0)
    assert len(fr.dump("x")) == 1


# ---------------------------------------------------------------------------
# dump triggers
# ---------------------------------------------------------------------------

def test_sigusr2_dump_trigger(tmp_path):
    """The classic black-box extraction signal: SIGUSR2 -> per-rank
    JSON under the configured directory."""
    fr.configure(directory=str(tmp_path), capacity=64, enabled=True)
    assert fr.install_signal_handler()
    fr.record(fr.SUBMIT, rank=0, name="sig.t", type="ALLREDUCE")
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.monotonic() + 5.0
    files = []
    while time.monotonic() < deadline and not files:
        # The dump is written as .tmp and renamed; wait for the rename.
        files = [f for f in os.listdir(str(tmp_path))
                 if "sigusr2" in f and not f.endswith(".tmp")]
        time.sleep(0.02)
    assert files, "SIGUSR2 did not produce a dump"
    with open(tmp_path / files[0]) as f:
        d = json.load(f)
    assert d["reason"] == "sigusr2"
    assert any(e["kind"] == "submit" for e in d["events"])


def test_trigger_dump_throttles_storms(tmp_path):
    fr.configure(directory=str(tmp_path), capacity=64, enabled=True)
    fr.record(fr.NOTE, rank=0)
    fr.trigger_dump("promotion")
    fr.trigger_dump("promotion")   # inside the throttle window
    files = [f for f in os.listdir(str(tmp_path))
             if "promotion" in f]
    assert len(files) == 1


def test_promotion_dump_trigger_via_real_kill(tmp_path):
    """A lost-rank promotion on the coordinator dumps the black box:
    2-rank world over the real control plane, rank 1 killed, grace
    expiry promotes -> blackbox-*.json appears with the promote event
    and the frame history leading up to it."""
    import threading

    from chaos_soak import ChaosWorld
    import numpy as np

    fr.configure(directory=str(tmp_path), capacity=4096, enabled=True)
    world = None
    try:
        world = ChaosWorld(2, stall_shutdown_s=4.0,
                           liveness_interval_s=0.3,
                           reconnect_grace_s=0.6)
        # One real collective (both ranks) so the ring holds frame
        # history before the fault.
        t1 = threading.Thread(
            target=world.collective,
            args=(1, "allreduce", "bb.t", np.ones(8, np.float32), 0,
                  10.0), daemon=True)
        t1.start()
        world.collective(0, "allreduce", "bb.t",
                         np.ones(8, np.float32), 0, 10.0)
        t1.join(timeout=10.0)
        world.kill_rank(1)
        deadline = time.monotonic() + 10.0
        files = []
        while time.monotonic() < deadline and not files:
            files = [f for f in os.listdir(str(tmp_path))
                     if "promotion" in f or "fatal" in f]
            time.sleep(0.05)
        assert files, "no dump after a rank promotion"
        dumps = blackbox_merge.load_dumps(str(tmp_path))
        all_events = [e for d in dumps for e in d["events"]]
        assert any(e["kind"] == "promote" and e.get("peer") == 1
                   for e in all_events), \
            "promote event missing from the dumps"
        assert any(e["kind"] == "frame_rx" for e in all_events), \
            "no frame history in the dumps"
    finally:
        if world is not None:
            world.close()


# ---------------------------------------------------------------------------
# the cross-rank merge
# ---------------------------------------------------------------------------

def _synthetic_dumps(skew_s: float):
    """Coordinator + one worker whose wall clock runs ``skew_s``
    ahead, exchanging HBs every second for 10 beats; the worker also
    records a promote-adjacent fatal to merge."""
    base = 1_000_000.0
    delay = 0.002
    coord_events = []
    worker_events = []
    for i in range(10):
        t = base + i * 1.0
        coord_events.append({"mono": i * 1.0, "wall": t,
                             "kind": "frame_tx", "rank": 0,
                             "role": "coord", "frame": "HB",
                             "nbytes": 0, "fanout": 1})
        worker_events.append({"mono": i * 1.0 + delay,
                              "wall": t + delay + skew_s,
                              "kind": "hb_rx", "rank": 1,
                              "role": "worker"})
        worker_events.append({"mono": i * 1.0 + 0.5,
                              "wall": t + 0.5 + skew_s,
                              "kind": "frame_tx", "rank": 1,
                              "role": "worker", "frame": "HB",
                              "nbytes": 6})
        coord_events.append({"mono": i * 1.0 + 0.5 + delay,
                             "wall": t + 0.5 + delay,
                             "kind": "hb_rx", "rank": 0,
                             "role": "coord", "peer": 1})
    worker_events.append({"mono": 10.0, "wall": base + 10.0 + skew_s,
                          "kind": "fatal", "rank": 1,
                          "role": "worker", "error": "boom"})
    coord_events.append({"mono": 10.5, "wall": base + 10.5,
                         "kind": "promote", "rank": 0, "role": "coord",
                         "peer": 1, "clean": False,
                         "reason": "liveness timeout"})
    mk = lambda rank, evs: {  # noqa: E731
        "version": 1, "reason": "unit", "rank": rank, "pid": 1,
        "mono_at_dump": 11.0, "wall_at_dump": base + 11.0,
        "events": evs}
    return [mk(0, coord_events), mk(1, worker_events)]


@pytest.mark.parametrize("skew_s", [0.0, 0.2, -0.15])
def test_clock_offset_estimation_on_skewed_ranks(tmp_path, skew_s):
    """NTP-style HB pairing recovers a worker's clock skew to within
    the one-way delay, so merged ordering is causal: the worker's
    fatal (true time 10.0) must land BEFORE the coordinator's promote
    (10.5) no matter the skew direction."""
    dumps = _synthetic_dumps(skew_s)
    offsets = blackbox_merge.estimate_offsets(dumps)
    assert offsets["0"] == 0.0
    assert abs(offsets["1"] - skew_s) < 0.01, offsets
    evs = blackbox_merge.merged_events(dumps, offsets)
    kinds = [(e["kind"], d["rank"]) for _, e, d in evs]
    assert kinds.index(("fatal", 1)) < kinds.index(("promote", 0))


def test_merge_builds_valid_trace_and_verdict(tmp_path):
    dumps = _synthetic_dumps(0.25)
    for d in dumps:
        with open(tmp_path / ("blackbox-rank%s-unit-1.json"
                              % d["rank"]), "w") as f:
            json.dump(d, f)
    trace, verdict = blackbox_merge.merge(str(tmp_path))
    assert validate_trace.validate_events(trace, merged=True) == []
    assert verdict["failed_rank"] == 1
    assert verdict["first_divergent_event"]["kind"] == "fatal"
    assert verdict["ranks"] == [0, 1]
    assert abs(verdict["clock_offsets"]["1"] - 0.25) < 0.01


def test_multiple_dumps_per_rank_union_preserves_old_evidence(
        tmp_path):
    """A promotion-trigger dump at fault time + a later drill-end dump
    whose ring evicted the pre-fault events: the merge must UNION
    them (dedup exact duplicates), never discard the older file — the
    pre-fault frame history is the whole point of the black box."""
    early = {"version": 1, "reason": "promotion", "rank": 0, "pid": 1,
             "mono_at_dump": 5.0, "wall_at_dump": 1005.0,
             "events": [
                 {"mono": 1.0, "wall": 1001.0, "kind": "frame_rx",
                  "rank": 0, "role": "coord", "peer": 1, "frame": "CH",
                  "seq": 7},
                 {"mono": 4.0, "wall": 1004.0, "kind": "promote",
                  "rank": 0, "role": "coord", "peer": 1,
                  "clean": False, "reason": "grace expired"}]}
    late = {"version": 1, "reason": "drill_end", "rank": 0, "pid": 1,
            "mono_at_dump": 9.0, "wall_at_dump": 1009.0,
            "events": [
                # The promote survived the ring; frame seq=7 did not.
                {"mono": 4.0, "wall": 1004.0, "kind": "promote",
                 "rank": 0, "role": "coord", "peer": 1,
                 "clean": False, "reason": "grace expired"},
                {"mono": 8.0, "wall": 1008.0, "kind": "ckpt",
                 "rank": 0, "phase": "restore", "step": 3}]}
    for i, d in enumerate([early, late]):
        with open(tmp_path / ("blackbox-rank0-%s-%d.json"
                              % (d["reason"], i + 1)), "w") as f:
            json.dump(d, f)
    dumps = blackbox_merge.load_dumps(str(tmp_path))
    assert len(dumps) == 1
    kinds = [e["kind"] for e in dumps[0]["events"]]
    assert kinds == ["frame_rx", "promote", "ckpt"]  # unioned, sorted
    assert kinds.count("promote") == 1               # deduped
    assert dumps[0]["reason"] == "drill_end"         # newest metadata


def test_relay_dump_clock_alignment():
    """A root-attached relay's dump pairs against the coordinator's
    per-relay hb_rx events, so a skewed relay clock is recovered like
    a worker's."""
    base, skew, delay = 2_000_000.0, 0.3, 0.001
    cev, rev = [], []
    for i in range(8):
        t = base + i
        cev.append({"mono": i * 1.0, "wall": t, "kind": "frame_tx",
                    "rank": 0, "role": "coord", "frame": "HB",
                    "nbytes": 0, "fanout": 2})
        rev.append({"mono": i + delay, "wall": t + delay + skew,
                    "kind": "hb_rx", "rank": "relay0",
                    "role": "relay"})
        rev.append({"mono": i + 0.5, "wall": t + 0.5 + skew,
                    "kind": "frame_tx", "rank": "relay0",
                    "role": "relay", "frame": "HB", "nbytes": 6})
        cev.append({"mono": i + 0.5 + delay, "wall": t + 0.5 + delay,
                    "kind": "hb_rx", "rank": 0, "role": "coord",
                    "relay": 0})
    mk = lambda rank, evs: {  # noqa: E731
        "version": 1, "reason": "unit", "rank": rank, "pid": 1,
        "mono_at_dump": 9.0, "wall_at_dump": base + 9.0,
        "events": evs}
    offsets = blackbox_merge.estimate_offsets([mk(0, cev),
                                               mk("relay0", rev)])
    assert abs(offsets["relay0"] - skew) < 0.01, offsets


def test_merge_cli_and_malformed_input(tmp_path):
    """The CLI writes trace + verdict and exits nonzero on garbage."""
    dumps = _synthetic_dumps(0.0)
    for d in dumps:
        with open(tmp_path / ("blackbox-rank%s-unit-1.json"
                              % d["rank"]), "w") as f:
            json.dump(d, f)
    trace_p = tmp_path / "trace.json"
    verdict_p = tmp_path / "verdict.json"
    rc = blackbox_merge.main([str(tmp_path), "-o", str(trace_p),
                              "--verdict", str(verdict_p)])
    assert rc == 0
    assert validate_trace.validate_file(str(trace_p),
                                        merged=True) == []
    with open(verdict_p) as f:
        assert json.load(f)["failed_rank"] == 1
    # Malformed dump -> nonzero, crisp error.
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "blackbox-rankX-x-1.json").write_text("{not json")
    assert blackbox_merge.main([str(bad)]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert blackbox_merge.main([str(empty)]) == 2
    # Valid JSON whose events lack wall/kind (truncated/foreign dump)
    # must fail as the same crisp MergeError, never a KeyError.
    trunc = tmp_path / "trunc"
    trunc.mkdir()
    (trunc / "blackbox-rank0-x-1.json").write_text(
        json.dumps({"rank": 0, "events": [{"x": 1}]}))
    assert blackbox_merge.main([str(trunc)]) == 2


# ---------------------------------------------------------------------------
# /blackbox endpoint auth
# ---------------------------------------------------------------------------

def test_blackbox_endpoint_rejects_without_job_secret():
    from horovod_tpu.common import metrics
    from horovod_tpu.runner import job_secret

    fr.configure(capacity=64, enabled=True)
    fr.record(fr.SUBMIT, rank=0, name="http.t", type="ALLREDUCE")
    secret = job_secret.make_secret_key()
    srv = metrics.serve(port=0, secret=secret)
    try:
        url = "http://127.0.0.1:%d/blackbox" % srv.port
        # Unsigned: rejected — a traffic log must never be an open
        # sidechannel when the job runs with a secret.
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=10)
        assert exc.value.code == 403
        # Wrong secret: rejected.
        ts = repr(time.time())
        bad = urllib.request.Request(url, headers={
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(
                "not-the-secret", "GET", "/blackbox", b"", ts)})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=10)
        assert exc.value.code == 403
        # Signed: the ring comes back as JSON.
        ts = repr(time.time())
        good = urllib.request.Request(url, headers={
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(
                secret, "GET", "/blackbox", b"", ts)})
        with urllib.request.urlopen(good, timeout=10) as r:
            body = json.loads(r.read().decode())
        assert body["reason"] == "http"
        assert any(e["kind"] == "submit" and e["name"] == "http.t"
                   for e in body["events"])
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the one-attribute-check perf pin (failpoints/liveness precedent)
# ---------------------------------------------------------------------------

def test_disabled_sites_never_call_record(monkeypatch, hvd_single):
    """Booby-trap: with the recorder disarmed, a real collective
    through runtime.submit must never get past the ENABLED guard."""
    import numpy as np

    assert not fr.ENABLED

    def boom(*a, **k):
        raise AssertionError("flight_recorder.record called while "
                             "disabled")

    monkeypatch.setattr(fr, "record", boom)
    out = np.asarray(hvd_single.allreduce(
        np.ones(8, np.float32), op=hvd_single.Sum, name="bb.disabled"))
    np.testing.assert_allclose(out, 1.0)


def test_enabled_site_records_through_the_runtime(hvd_single):
    """Inverse control: armed, the same path records the submission."""
    import numpy as np

    fr.configure(capacity=256, enabled=True)
    hvd_single.allreduce(np.ones(4, np.float32), op=hvd_single.Sum,
                         name="bb.enabled")
    assert any(e[2] == fr.SUBMIT and e[4].get("name") == "bb.enabled"
               for e in fr.events())


def test_disabled_path_overhead_stays_one_attribute_check():
    """With the recorder disarmed a site costs ONE module-attribute
    check — same bound as the failpoints pin (~20x measured cost,
    loose for CI noise, tight against reintroduced per-call work)."""
    import timeit

    assert not fr.ENABLED
    n = 200_000
    per_call = timeit.timeit(
        "fr.ENABLED and fr.record('perf.site')",
        globals={"fr": fr}, number=n) / n
    assert per_call < 1e-6, \
        "disabled flight-recorder guard costs %.0f ns/op (>1 us): no " \
        "longer a bare attribute check" % (per_call * 1e9)
