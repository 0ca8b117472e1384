"""Continuous sampling profiler (common/profiler.py): sampler + lane
classification, park-point filtering, the rank-labeled MR digest and
its fanout-2 survival, triggered captures, the /profile endpoint's
job-secret parity with /metrics and /status, the one-attribute-check
disabled cost (booby-trap + timeit), flame.py CLI exit codes, and the
hvdtop --profile pane (docs/observability.md)."""

import contextlib
import io
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
sys.path.insert(0, REPO)

from horovod_tpu.common import failpoints as fp  # noqa: E402
from horovod_tpu.common import metrics  # noqa: E402
from horovod_tpu.common import profiler as prof  # noqa: E402
from horovod_tpu.common import slo  # noqa: E402
from horovod_tpu.common import straggler as sg  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm(monkeypatch):
    # The hot-share gauge is rank×k×frame labeled: an earlier test's
    # digest (e.g. a drill in another file) would otherwise bleed into
    # this file's extractions, so start from a clean registry too.
    metrics.REGISTRY.reset()
    # A pytest-xdist worker has a thread of the runner's own, execnet's
    # receiver, blocked in ``gateway_base:read`` for the whole session.
    # The sampler sees a leaf frame and not the syscall under it, so
    # that thread is in every sample and ties with the busy thread for
    # the top of the digest.  It is idle plumbing like the stdlib's:
    # say so here, where the runner is known, and assert as before.
    monkeypatch.setattr(prof, "_IDLE_MODULES",
                        prof._IDLE_MODULES | {"gateway_base"})
    for mod in (prof, slo, sg, fp):
        mod.reset()
    yield
    for mod in (prof, slo, sg, fp):
        mod.reset()


def _busy(stop: threading.Event):
    # A pure-Python spin: always on-CPU with this frame as the leaf,
    # so the sampler must rank it as the dominant active frame.
    x = 0
    while not stop.is_set():
        x += 1
    return x


@contextlib.contextmanager
def _busy_thread():
    stop = threading.Event()
    t = threading.Thread(target=_busy, args=(stop,), daemon=True,
                         name="busyworker")
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(timeout=2.0)


def _wait_samples(n: int, timeout_s: float = 5.0):
    # Park on an Event (not time.sleep): the sampler classifies a
    # threading.Event.wait leaf as parked, so this poll loop never
    # pollutes the hot digest the tests assert on.
    pause = threading.Event()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        p = prof.instance()
        if p is not None and p.profile_dict()["samples"] >= n:
            return
        pause.wait(0.02)
    raise AssertionError("profiler never reached %d samples" % n)


# ---------------------------------------------------------------------------
# sampler: stacks, lanes, park-point filtering
# ---------------------------------------------------------------------------

def test_sampler_names_the_busy_frame_and_parks_waiters():
    prof.configure(enabled=True, hz=200.0, topk=5)
    parked = threading.Event()
    waiter = threading.Thread(target=parked.wait, daemon=True,
                              name="parkedworker")
    waiter.start()
    try:
        with _busy_thread():
            _wait_samples(20)
            d = prof.profile_dict()
    finally:
        parked.set()
        waiter.join(timeout=2.0)
    assert d["enabled"] and d["samples"] >= 20
    top = d["top"]
    assert top, "no hot frames collected"
    # The spin loop dominates; the Event.wait-parked thread (stdlib
    # threading leaf) must NOT appear in the hot digest at all.
    assert top[0]["frame"].endswith(":_busy")
    assert all("wait" not in e["frame"] for e in top)
    # Collapsed stacks: thread-name root, ;-joined, flame-ready.
    hot = [s for s in d["collapsed"] if s.endswith(":_busy")]
    assert hot and hot[0].startswith("busyworker:thread;")
    assert d["blocking_share"] > 0.0  # the parked waiter counts there


def test_triggered_capture_freezes_the_window_and_counts():
    prof.configure(enabled=True, hz=200.0)
    with _busy_thread():
        _wait_samples(10)
        prof.trigger_capture("straggler", "rank 3 score 5.0")
        d = prof.profile_dict()
    cap = d["last_capture"]
    assert cap is not None and cap["reason"] == "straggler"
    assert cap["top"] and cap["window_samples"] > 0
    assert metrics.REGISTRY.counter(
        "hvd_prof_captures_total").value(reason="straggler") >= 1
    # Throttled: an immediate second trigger is dropped, not queued.
    assert prof.instance().capture("stall", "again") is None


# ---------------------------------------------------------------------------
# MR digest: publish -> snapshot -> extract, and fanout-2 survival
# ---------------------------------------------------------------------------

def test_digest_publish_extract_roundtrip_and_describe():
    prof.configure(enabled=True, hz=200.0, topk=3)
    with _busy_thread():
        _wait_samples(20)
        prof.publish_digest(rank=5)
    digest = prof.digest_from_snapshot(metrics.snapshot())
    assert 5 in digest
    entries = digest[5]
    assert [e["k"] for e in entries] == sorted(e["k"] for e in entries)
    assert entries[0]["frame"].endswith(":_busy")
    assert 0.0 < entries[0]["share"] <= 1.0
    text = prof.describe_digest(entries)
    assert ":_busy" in text and "lane" in text and "% of samples" in text
    assert prof.describe_digest([]) == ""


def test_publish_digest_retires_stale_frames():
    """A rank's hot set drifts between publishes; the previous (k,
    frame) children must not shadow the fresh digest — and other
    ranks' children must survive the retirement untouched."""
    g = metrics.gauge("hvd_prof_hot_share")
    g.set(0.9, rank=5, k=0, lane="submit", frame="old:frame")
    g.set(0.8, rank=3, k=0, lane="submit", frame="other:frame")
    prof.configure(enabled=True, hz=200.0, topk=3)
    with _busy_thread():
        _wait_samples(20)
        prof.publish_digest(rank=5)
    digest = prof.digest_from_snapshot(metrics.snapshot())
    assert all(e["frame"] != "old:frame" for e in digest[5])
    assert digest[5][0]["frame"].endswith(":_busy")
    assert digest[3][0]["frame"] == "other:frame"


def test_digest_labels_survive_fanout2_subtree_merges():
    """The MR→MA contract for the profile digest: each rank publishes
    only its own rank label, so two relay pre-merges + the root merge
    preserve every rank's top-K rows intact."""
    def rank_snap(rank):
        reg = metrics.MetricsRegistry()
        g = reg.gauge("hvd_prof_hot_share")
        g.set(0.10 * (rank + 1), rank=rank, k=0, lane="submit",
              frame="failpoints:maybe_fail")
        g.set(0.01 * (rank + 1), rank=rank, k=1, lane="controller",
              frame="relay:recv_frame")
        return reg.snapshot()

    left = metrics.merge_snapshots([rank_snap(r) for r in range(4)])
    right = metrics.merge_snapshots([rank_snap(r)
                                     for r in range(4, 8)])
    root = metrics.merge_snapshots([left, right])
    digest = prof.digest_from_snapshot(root)
    assert sorted(digest) == list(range(8))
    for r in range(8):
        assert digest[r][0]["frame"] == "failpoints:maybe_fail"
        assert digest[r][0]["share"] == pytest.approx(0.10 * (r + 1))
        assert digest[r][1]["lane"] == "controller"


# ---------------------------------------------------------------------------
# GET /profile: the job-secret parity contract (/metrics, /status)
# ---------------------------------------------------------------------------

def test_profile_endpoint_guarded_and_404_without_provider():
    from horovod_tpu.runner import job_secret

    secret = job_secret.make_secret_key()
    srv = metrics.serve(port=0, registry=metrics.MetricsRegistry(),
                        secret=secret,
                        profile_provider=prof.profile_dict)
    try:
        url = "http://127.0.0.1:%d/profile" % srv.port
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=10)
        assert exc.value.code == 403
        ts = repr(time.time())
        good = urllib.request.Request(url, headers={
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(secret, "GET",
                                               "/profile", b"", ts)})
        with urllib.request.urlopen(good, timeout=10) as r:
            body = json.loads(r.read().decode())
        # Disarmed profiler: self-describing, still a valid payload.
        assert body == {"enabled": False}
    finally:
        srv.stop()
    bare = metrics.serve(port=0, registry=metrics.MetricsRegistry(),
                         secret="")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                "http://127.0.0.1:%d/profile" % bare.port, timeout=10)
        assert exc.value.code == 404
    finally:
        bare.stop()


def test_profile_endpoint_serves_live_payload():
    prof.configure(enabled=True, hz=200.0)
    srv = metrics.serve(port=0, registry=metrics.MetricsRegistry(),
                        secret="", profile_provider=prof.profile_dict)
    try:
        with _busy_thread():
            _wait_samples(10)
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/profile" % srv.port,
                    timeout=10) as r:
                body = json.loads(r.read().decode())
    finally:
        srv.stop()
    assert body["enabled"] and body["samples"] >= 10
    assert body["collapsed"] and body["top"]


# ---------------------------------------------------------------------------
# the one-attribute-check perf pins
# ---------------------------------------------------------------------------

def test_disabled_sites_never_touch_the_profiler(monkeypatch,
                                                hvd_single):
    """Booby-trap: with the profiler disarmed, a real collective must
    never get past the ENABLED guards at any feeder site."""
    assert not prof.ENABLED

    def boom(*a, **k):
        raise AssertionError("profiler touched while disabled")

    monkeypatch.setattr(prof, "trigger_capture", boom)
    monkeypatch.setattr(prof, "publish_digest", boom)
    monkeypatch.setattr(prof.SamplingProfiler, "capture", boom)
    out = np.asarray(hvd_single.allreduce(
        np.ones(8, np.float32), op=hvd_single.Sum,
        name="prof.disabled"))
    np.testing.assert_allclose(out, 1.0)


def test_disabled_path_overhead_stays_one_attribute_check():
    import timeit

    assert not prof.ENABLED
    n = 200_000
    per_call = timeit.timeit(
        "prof.ENABLED and prof.trigger_capture('stall', '')",
        globals={"prof": prof}, number=n) / n
    assert per_call < 1e-6, \
        "disabled profiler guard costs %.0f ns/op (>1 us): no " \
        "longer a bare attribute check" % (per_call * 1e9)


# ---------------------------------------------------------------------------
# stall warnings carry the root cause
# ---------------------------------------------------------------------------

def test_stall_warning_names_the_dominant_frame(caplog):
    import logging

    from horovod_tpu.common.stall_inspector import StallInspector

    si = StallInspector(warning_time_s=0.0, world_size=4)
    si.set_straggler_provider(lambda: (3, 5.5))
    si.set_root_cause_provider(
        lambda r: "failpoints:maybe_fail (submit lane, 88% of "
                  "samples)" if r == 3 else None)
    si.record_uncached_tensor("slow/w", 0)
    time.sleep(0.01)
    with caplog.at_level(logging.WARNING, "horovod_tpu.stall"):
        invalidate = si.check()
    assert invalidate == ["slow/w"]
    msg = "\n".join(r.getMessage() for r in caplog.records)
    assert "top straggler: rank 3" in msg
    assert "dominant frame: failpoints:maybe_fail" in msg


# ---------------------------------------------------------------------------
# flame.py: merge + render CLI (the blackbox_merge exit-code contract)
# ---------------------------------------------------------------------------

def _profile_file(tmp_path, rank, stacks):
    p = tmp_path / ("prof-r%d.json" % rank)
    p.write_text(json.dumps({
        "enabled": True, "rank": rank, "thread_samples": sum(
            stacks.values()), "collapsed": stacks}))
    return str(p)


def test_flame_merges_ranks_and_renders(tmp_path):
    import flame

    a = _profile_file(tmp_path, 0,
                      {"main:thread;runtime:_run_once": 6})
    b = _profile_file(tmp_path, 1,
                      {"main:thread;failpoints:maybe_fail": 14})
    out = tmp_path / "job.collapsed"
    svg = tmp_path / "job.svg"
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = flame.main([a, b, "-o", str(out), "--svg", str(svg)])
    assert rc == 0
    text = out.read_text()
    assert "rank 0;main:thread;runtime:_run_once 6" in text
    assert "rank 1;main:thread;failpoints:maybe_fail 14" in text
    body = svg.read_text()
    assert body.startswith("<svg") and "maybe_fail" in body
    assert "20 samples" in buf.getvalue()  # merged total


def test_flame_exits_2_on_bad_input(tmp_path):
    import flame

    # Unreadable path.
    assert flame.main([str(tmp_path / "missing.json")]) == 2
    # Valid JSON that is not a /profile payload.
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"hello": 1}))
    assert flame.main([str(junk)]) == 2
    # A real payload with zero samples: fail crisply, not blank SVG.
    empty = _profile_file(tmp_path, 0, {})
    assert flame.main([empty]) == 2


# ---------------------------------------------------------------------------
# hvdtop --profile pane
# ---------------------------------------------------------------------------

def _canned_status_with_profile():
    return {
        "rank": 0, "size": 2, "replay": {}, "queue_depth": 0,
        "ops_dispatched": 1,
        "cluster": {
            "size": 2, "formed": True, "broken": False,
            "pending_tensors": 0,
            "straggler": {"threshold": 4.0, "flagged": []},
            "ranks": {
                "0": {"state": "alive", "score": 0.0},
                "1": {"state": "alive", "score": 1.0,
                      "hot_frame": "failpoints:maybe_fail [submit]"},
            },
            "profile": {
                "1": [{"k": 0, "lane": "submit",
                       "frame": "failpoints:maybe_fail",
                       "share": 0.88}],
            }}}


def test_hvdtop_profile_pane_renders_digest():
    import hvdtop

    srv = metrics.serve(port=0, registry=metrics.MetricsRegistry(),
                        secret="",
                        status_provider=_canned_status_with_profile)
    try:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = hvdtop.main(["--once", "--profile", "--url",
                              "http://127.0.0.1:%d" % srv.port])
        out = buf.getvalue()
    finally:
        srv.stop()
    assert rc == 0
    assert "profile digest" in out
    assert "failpoints:maybe_fail" in out
    assert "failpoints:maybe_fail [submit]" in out  # hot-frame column
    # Without the flag the pane stays off (the default frame).
    srv2 = metrics.serve(port=0, registry=metrics.MetricsRegistry(),
                         secret="",
                         status_provider=_canned_status_with_profile)
    try:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = hvdtop.main(["--once", "--url",
                              "http://127.0.0.1:%d" % srv2.port])
        assert rc == 0 and "profile digest" not in buf.getvalue()
    finally:
        srv2.stop()


# ---------------------------------------------------------------------------
# e2e: the drill verdict names the injected delay site
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_drill_root_cause_names_the_injected_delay_site():
    # Marked slow: the tier-1 negotiation drill in test_straggler.py
    # already asserts root_cause_named on the same drill record; this
    # standalone variant exists for chaos runs and deeper digests.
    """Acceptance: WHO (straggler naming) is joined by WHY — the
    drill's profile digests must name failpoints:maybe_fail (where the
    injected delay actually sleeps) as the dominant frame."""
    from chaos_soak import run_straggler_drill

    rec = run_straggler_drill(mode="negotiation", ranks=8, victim=3,
                              delay_ms=25.0, seed=0,
                              serve_status=True)
    assert rec["ok"], {k: rec.get(k) for k in
                       ("named", "tta_s", "victim_score", "hangs",
                        "errors", "hvdtop_rc")}
    assert rec["root_cause_named"], rec.get("root_cause")
    assert "maybe_fail" in rec["root_cause"]
    assert rec["ttrc_s"] is not None and rec["ttrc_s"] < 20.0
    # The --profile pane rode the drill's hvdtop --once invocation.
    assert any("profile digest" in line
               for line in rec["hvdtop_lines"])
