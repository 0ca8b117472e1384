"""Online serving plane (horovod_tpu/serve/): snapshot-consistent
bootstrap + tail, torn-apply impossibility under the serve.delta_apply
failpoint, staleness-bound rejection, bootstrap past a corrupt chain,
HTTP auth parity with the other operator endpoints, and the
train-commit-serve-verify smoke."""

import glob
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from horovod_tpu.checkpoint import (CheckpointManager, RowDelta,
                                    assemble_table)
from horovod_tpu.common import env as henv
from horovod_tpu.common import failpoints, metrics
from horovod_tpu.runner import job_secret
from horovod_tpu.serve import ServeServer, ServingReplica, StalenessError
import horovod_tpu.serve as serve_pkg


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    failpoints.set_crash_handler(None)
    yield
    failpoints.reset()
    failpoints.set_crash_handler(None)


# ---------------------------------------------------------------------------
# Closed-form single-rank trainer: a 32x4 table whose value at every
# step is computable without replaying history in the assertions.
# ---------------------------------------------------------------------------

_ROWS, _DIM = 32, 4
_ITEM = "sparse/tbl/rows.r00000"


def _base_table():
    return (np.arange(_ROWS * _DIM, dtype=np.float32)
            .reshape(_ROWS, _DIM) * 0.01)


def _touched(step):
    return np.unique((np.arange(6) * 5 + step * 3) % _ROWS)


def _update(step, rows):
    vals = np.repeat(rows.astype(np.float32)[:, None], _DIM, axis=1)
    return vals + step / 100.0


def _table_at(step):
    t = _base_table()
    for s in range(2, step + 1):
        r = _touched(s)
        t[r] = _update(s, r)
    return t


def _commit(mgr, step):
    """Commit one step: full base at step 1, RowDelta after."""
    if step == 1:
        item = RowDelta(np.arange(_ROWS), _base_table(), _ROWS)
        mgr.save(1, {"dense/x": np.float32(1)},
                 local_items={_ITEM: item})
    else:
        r = _touched(step)
        item = RowDelta(r, _update(step, r), _ROWS)
        mgr.save(step, {"dense/x": np.float32(step)},
                 local_items={_ITEM: item}, delta_of=mgr.delta_plan())


@pytest.fixture
def mgr(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=None)
    yield m
    m.close()


# ---------------------------------------------------------------------------
# bootstrap + tail + reads
# ---------------------------------------------------------------------------

def test_bootstrap_tail_lookup_and_bag(tmp_path, mgr):
    _commit(mgr, 1)
    rep = ServingReplica(str(tmp_path))
    assert rep.bootstrap() == 1
    assert rep.table_names() == ["tbl"]
    _commit(mgr, 2)
    _commit(mgr, 3)
    assert rep.poll_once() == 2        # two incremental delta applies
    ids = np.array([0, 5, _touched(3)[0], 31])
    rows, step = rep.lookup("tbl", ids)
    assert step == 3
    assert np.array_equal(rows, _table_at(3)[ids])
    served, latest = rep.freshness()
    assert (served, latest) == (3, 3)
    assert metrics.REGISTRY.gauge(
        "hvd_serve_freshness_steps").value() == 0.0
    # Pooled read replicates the EmbeddingBag shapes bit-for-bit.
    ids = np.array([1, 2, 7, 7, 9])
    offsets = np.array([0, 2, 2, 4])   # example 1 is empty
    pooled, step = rep.embedding_bag("tbl", ids, offsets, mode="mean")
    t = _table_at(3)
    assert step == 3
    assert np.array_equal(pooled[0], (t[1] + t[2]) / 2.0)
    assert np.array_equal(pooled[1], np.zeros(_DIM, np.float32))
    assert np.array_equal(pooled[2], t[7])     # mean of {7, 7}
    assert np.array_equal(pooled[3], t[9])
    with pytest.raises(KeyError):
        rep.lookup("nope", [0])
    with pytest.raises(IndexError):
        rep.lookup("tbl", [_ROWS + 7])


def test_torn_apply_structurally_impossible(tmp_path, mgr):
    """serve.delta_apply fires between snapshot build and install:
    whether it errors or drops the flip, every read before/after sees
    a WHOLE committed step — never a half-applied delta."""
    _commit(mgr, 1)
    rep = ServingReplica(str(tmp_path))
    rep.bootstrap()
    _commit(mgr, 2)
    failpoints.configure("serve.delta_apply=error(torn,times=1)")
    assert rep.poll_once() == 0        # advance failed mid-apply
    rows, step = rep.lookup("tbl", np.arange(_ROWS))
    assert step == 1                   # old snapshot, fully intact
    assert np.array_equal(rows, _table_at(1))
    # The freshness plane still saw the committed step it cannot serve.
    assert rep.freshness() == (1, 2)
    failpoints.reset()
    failpoints.configure("serve.delta_apply=drop(1)")
    assert rep.poll_once() == 0        # flip dropped, same story
    rows, step = rep.lookup("tbl", np.arange(_ROWS))
    assert step == 1
    assert np.array_equal(rows, _table_at(1))
    failpoints.reset()
    assert rep.poll_once() == 1        # now the flip lands, atomically
    rows, step = rep.lookup("tbl", np.arange(_ROWS))
    assert step == 2
    assert np.array_equal(rows, _table_at(2))


def test_staleness_bound_rejects_reads(tmp_path, mgr, monkeypatch):
    _commit(mgr, 1)
    rep = ServingReplica(str(tmp_path))
    rep.bootstrap()
    for s in (2, 3, 4):
        _commit(mgr, s)
    monkeypatch.setenv(henv.HOROVOD_SERVE_MAX_STALENESS_STEPS, "1")
    failpoints.configure("serve.delta_apply=drop(10)")
    rep.poll_once()                    # learns latest=4, cannot apply
    before = metrics.REGISTRY.counter(
        "hvd_serve_rejects_total").value(reason="staleness")
    with pytest.raises(StalenessError):
        rep.lookup("tbl", [0])
    assert metrics.REGISTRY.counter(
        "hvd_serve_rejects_total").value(
            reason="staleness") == before + 1
    failpoints.reset()
    rep.poll_once()                    # catches up; reads flow again
    rows, step = rep.lookup("tbl", [0, 1])
    assert step == 4
    assert np.array_equal(rows, _table_at(4)[[0, 1]])


def test_bootstrap_past_corrupt_chain_tip(tmp_path, mgr):
    for s in (1, 2, 3):
        _commit(mgr, s)
    shard = glob.glob(str(tmp_path / "step-0000000003"
                          / "shard-*.bin"))[0]
    with open(shard, "r+b") as f:
        f.seek(40)
        f.write(b"\x13\x37\x13\x37")
    rep = ServingReplica(str(tmp_path))
    assert rep.bootstrap() == 2        # fell back past the bad tip
    rows, step = rep.lookup("tbl", np.arange(_ROWS))
    assert step == 2
    assert np.array_equal(rows, _table_at(2))
    # Tailing cannot advance through the corrupt link either — the
    # replica keeps serving the last good step instead of dying.
    _commit(mgr, 4)
    assert rep.poll_once() == 0
    rows, step = rep.lookup("tbl", np.arange(_ROWS))
    assert step == 2
    assert np.array_equal(rows, _table_at(2))


# ---------------------------------------------------------------------------
# HTTP endpoint: auth parity with /metrics //status //profile
# ---------------------------------------------------------------------------

def _post(url, body, headers=None):
    req = urllib.request.Request(url, data=body,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read().decode())


def test_http_lookup_auth_parity_and_freshness(tmp_path, mgr):
    _commit(mgr, 1)
    _commit(mgr, 2)
    rep = ServingReplica(str(tmp_path))
    rep.bootstrap()
    rep.poll_once()
    secret = job_secret.make_secret_key()
    srv = ServeServer(rep, port=0, secret=secret)
    try:
        url = "http://127.0.0.1:%d/lookup" % srv.port
        body = json.dumps({"table": "tbl", "ids": [0, 3, 31]}).encode()
        # unsigned -> 403 (secret armed)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url, body)
        assert exc.value.code == 403
        # signed -> 200 with step-stamped rows
        ts = repr(time.time())
        out = _post(url, body, {
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(secret, "POST",
                                               "/lookup", body, ts)})
        assert out["step"] == 2
        assert np.allclose(np.asarray(out["rows"], np.float32),
                           _table_at(2)[[0, 3, 31]])
        # pooled read over HTTP
        body = json.dumps({"table": "tbl", "ids": [1, 2],
                           "offsets": [0], "mode": "sum"}).encode()
        ts = repr(time.time())
        out = _post(url, body, {
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(secret, "POST",
                                               "/lookup", body, ts)})
        t = _table_at(2)
        assert np.allclose(np.asarray(out["rows"], np.float32),
                           (t[1] + t[2])[None, :])
        # freshness endpoint under the same auth contract
        furl = "http://127.0.0.1:%d/freshness" % srv.port
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(furl, timeout=10)
        assert exc.value.code == 403
        ts = repr(time.time())
        req = urllib.request.Request(furl, headers={
            job_secret.TS_HEADER: ts,
            job_secret.HEADER: job_secret.sign(secret, "GET",
                                               "/freshness", b"", ts)})
        with urllib.request.urlopen(req, timeout=10) as r:
            fresh = json.loads(r.read().decode())
        assert fresh["served_step"] == 2
        assert fresh["tables"] == ["tbl"]
    finally:
        srv.stop()
    # bare server (no replica wired) -> 404, exactly like a metrics
    # server without a profile provider
    bare = ServeServer(None, port=0, secret="")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post("http://127.0.0.1:%d/lookup" % bare.port,
                  json.dumps({"table": "tbl", "ids": [0]}).encode())
        assert exc.value.code == 404
    finally:
        bare.stop()


def test_http_staleness_maps_to_503(tmp_path, mgr, monkeypatch):
    _commit(mgr, 1)
    rep = ServingReplica(str(tmp_path))
    rep.bootstrap()
    for s in (2, 3):
        _commit(mgr, s)
    monkeypatch.setenv(henv.HOROVOD_SERVE_MAX_STALENESS_STEPS, "1")
    failpoints.configure("serve.delta_apply=drop(10)")
    rep.poll_once()
    srv = ServeServer(rep, port=0, secret="")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post("http://127.0.0.1:%d/lookup" % srv.port,
                  json.dumps({"table": "tbl", "ids": [0]}).encode())
        assert exc.value.code == 503
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# train-commit-serve-verify smoke (tier-1, ~seconds)
# ---------------------------------------------------------------------------

def test_train_commit_serve_verify_smoke(tmp_path, monkeypatch):
    """The whole pipeline in one process: a trainer thread commits a
    delta chain while the replica's tail thread follows; every
    concurrent read must equal the closed-form table at its OWN step
    stamp, and equal what a fresh read-only manager restores at that
    step — the bit-consistency contract of docs/serving.md."""
    monkeypatch.setenv(henv.HOROVOD_SERVE_POLL_SECONDS, "0.02")
    m = CheckpointManager(str(tmp_path), keep=None)
    _commit(m, 1)
    plane = serve_pkg.start(str(tmp_path), http=False)
    stop = threading.Event()
    errs = []

    def trainer():
        try:
            for s in range(2, 9):
                _commit(m, s)
                time.sleep(0.03)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=trainer)
    t.start()
    reads = 0
    served = {}
    while not stop.is_set() or reads == 0:
        ids = np.array([0, 3, 17, 31])
        rows, step = plane.replica.lookup("tbl", ids)
        assert np.array_equal(rows, _table_at(step)[ids]), \
            "torn/stale read at served step %d" % step
        served[step] = (ids, rows)
        reads += 1
        time.sleep(0.005)
    t.join(timeout=30)
    assert not t.is_alive()
    assert not errs, errs
    # Each served step's rows against the committed chain itself,
    # restored by a manager that never saw the trainer's state.
    ro = CheckpointManager(str(tmp_path), rank=0, world_size=1,
                           keep=None)
    for step, (ids, rows) in served.items():
        tab = assemble_table(ro.restore(step), "sparse/tbl/rows")
        assert np.array_equal(tab[ids], rows), step
    ro.close()
    deadline = time.monotonic() + 10.0
    while (plane.replica.freshness()[0] < 8
           and time.monotonic() < deadline):
        time.sleep(0.02)
    assert plane.replica.freshness()[0] == 8
    rows, step = plane.replica.lookup("tbl", np.arange(_ROWS))
    assert step == 8
    assert np.array_equal(rows, _table_at(8))
    assert reads > 0
    plane.stop()
    m.close()
