"""Launcher unit tests: host/slot math, CLI parsing, rendezvous KV.

Mirrors the reference's test/single/test_run.py strategy (SURVEY §4:
launcher logic is tested in-process with no cluster).
"""

import os
import textwrap

import pytest

from horovod_tpu.runner import (HostInfo, RendezvousClient,
                                RendezvousServer, get_host_assignments,
                                parse_hosts, parse_host_files,
                                slot_env_vars)
from horovod_tpu.runner.launch import parse_args


# ---------------------------------------------------------------------
# hosts / slots
# ---------------------------------------------------------------------
def test_parse_hosts():
    hosts = parse_hosts("worker-0:2,worker-1:4")
    assert hosts == [HostInfo("worker-0", 2), HostInfo("worker-1", 4)]


def test_parse_hosts_invalid():
    with pytest.raises(ValueError):
        parse_hosts("worker-0")
    with pytest.raises(ValueError):
        parse_hosts("worker 0:2")


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("# comment\nhost-a slots=4\nhost-b slots=2\n")
    assert parse_host_files(str(f)) == "host-a:4,host-b:2"


def test_host_assignments_basic():
    slots = get_host_assignments(parse_hosts("a:2,b:2"), 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.hostname for s in slots] == ["a", "a", "b", "b"]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.size == 4 for s in slots)
    assert all(s.local_size == 2 for s in slots)
    assert all(s.cross_size == 2 for s in slots)


def test_host_assignments_max_np_truncates():
    slots = get_host_assignments(parse_hosts("a:4,b:4"), 2, max_np=3)
    assert len(slots) == 3
    assert [s.hostname for s in slots] == ["a", "a", "a"]
    assert slots[0].size == 3


def test_host_assignments_uneven_cross_size():
    # b has no slot at local_rank 2,3 -> cross_size differs per local.
    slots = get_host_assignments(parse_hosts("a:4,b:2"), 6)
    by_rank = {s.rank: s for s in slots}
    assert by_rank[0].cross_size == 2     # local_rank 0 on both hosts
    assert by_rank[2].cross_size == 1     # local_rank 2 only on a
    assert by_rank[4].hostname == "b"
    assert by_rank[4].cross_rank == 1


def test_host_assignments_min_np_error():
    with pytest.raises(ValueError):
        get_host_assignments(parse_hosts("a:2"), 4)


def test_slot_env_vars():
    slots = get_host_assignments(parse_hosts("a:2"), 2)
    env = slot_env_vars(slots[1])
    assert env["HOROVOD_RANK"] == "1"
    assert env["HOROVOD_SIZE"] == "2"
    assert env["HOROVOD_LOCAL_RANK"] == "1"
    assert env["HOROVOD_HOSTNAME"] == "a"


def test_tpu_chip_env_one_chip_per_local_slot():
    """Four local slots get four distinct chips and one consistent
    peer list; one slot gets none of it (that process owns every chip);
    a count with no known grid is refused on a TPU host only."""
    from horovod_tpu.runner.hosts import tpu_chip_env
    ports = [7001, 7002, 7003, 7004]
    slots = get_host_assignments(parse_hosts("localhost:4"), 4)
    envs = [tpu_chip_env(s, ports) for s in slots]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["TPU_PROCESS_PORT"] for e in envs] == \
        ["7001", "7002", "7003", "7004"]
    for e in envs:
        assert e["TPU_PROCESS_ADDRESSES"] == \
            "localhost:7001,localhost:7002,localhost:7003,localhost:7004"
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"

    one = get_host_assignments(parse_hosts("localhost:1"), 1)[0]
    assert tpu_chip_env(one, ports[:1]) == {}
    # One slot on each of several hosts: each owns its host's chips.
    pod = get_host_assignments(parse_hosts("h1:1,h2:1"), 2)
    assert [tpu_chip_env(s, ports[:1]) for s in pod] == [{}, {}]

    # Layouts with no known grid: 2 or 3 slots, 4 slots on two hosts.
    for hosts, n in (("localhost:2", 2), ("localhost:3", 3),
                     ("h1:4,h2:4", 8)):
        slot = get_host_assignments(parse_hosts(hosts), n)[0]
        assert tpu_chip_env(slot, ports) == {}
        with pytest.raises(ValueError, match="local slots"):
            tpu_chip_env(slot, ports, tpu_host=True)


# ---------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------
def test_parse_args_basic():
    args = parse_args(["-np", "4", "-H", "h1:2,h2:2", "python",
                       "train.py"])
    assert args.np == 4
    assert args.hosts == "h1:2,h2:2"
    assert args.command == ["python", "train.py"]


def test_parse_args_tunables():
    args = parse_args(["-np", "2", "--fusion-threshold-mb", "32",
                       "--cycle-time-ms", "2.5", "--autotune",
                       "--timeline-filename", "/tmp/tl.json", "x"])
    assert args.fusion_threshold_mb == 32
    assert args.cycle_time_ms == 2.5
    assert args.autotune is True
    assert args.timeline_filename == "/tmp/tl.json"


def test_parse_args_elastic():
    args = parse_args(["-np", "2", "--min-np", "2", "--max-np", "4",
                       "--host-discovery-script", "./d.sh", "x"])
    assert args.min_np == 2
    assert args.max_np == 4
    assert args.host_discovery_script == "./d.sh"


def test_parse_args_config_file_and_override(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(textwrap.dedent("""
        params:
          fusion_threshold_mb: 16
          cycle_time_ms: 3.0
        autotune:
          enabled: true
        """))
    # CLI --cycle-time-ms must beat the config file; fusion comes from
    # the file (reference: config_parser.set_args_from_config).
    args = parse_args(["-np", "2", "--config-file", str(cfg),
                       "--cycle-time-ms", "7.0", "x"])
    assert args.fusion_threshold_mb == 16
    assert args.cycle_time_ms == 7.0
    assert args.autotune is True


def test_env_from_args():
    from horovod_tpu.runner.config_parser import env_from_args
    args = parse_args(["-np", "2", "--fusion-threshold-mb", "32",
                       "--no-stall-check", "x"])
    env = env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"


# ---------------------------------------------------------------------
# rendezvous KV store
# ---------------------------------------------------------------------
def test_rendezvous_put_get_delete():
    server = RendezvousServer()
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port)
        assert client.get("global", "k") is None
        client.put("global", "k", b"hello")
        assert client.get("global", "k") == b"hello"
        client.put("local_h1", "k", b"scoped")
        assert client.get("local_h1", "k") == b"scoped"
        assert client.get("global", "k") == b"hello"
        client.delete("global")
        assert server.kvstore.is_finalized("global")
    finally:
        server.stop()


def test_rendezvous_wait_get():
    import threading
    import time
    server = RendezvousServer()
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port)

        def put_later():
            time.sleep(0.3)
            client.put("s", "late", b"v")

        threading.Thread(target=put_later, daemon=True).start()
        assert client.wait_get("s", "late", timeout=5.0) == b"v"
        with pytest.raises(TimeoutError):
            client.wait_get("s", "never", timeout=0.3)
    finally:
        server.stop()


def test_check_build_flag(capsys):
    import sys
    from unittest import mock
    from horovod_tpu.runner import launch
    with mock.patch.object(sys, "argv", ["horovodrun", "--check-build"]):
        launch.run_commandline()
    out = capsys.readouterr().out
    assert "Available Frameworks" in out
    assert "[X] JAX" in out
    assert "Available Controllers" in out
    assert "RING" in out


def test_rendezvous_hmac_auth(monkeypatch):
    """With a job secret in force, the KV server accepts only
    HMAC-signed requests (reference: runner/common/util/secret.py +
    network.py message verification): a signing client round-trips,
    unsigned or wrong-key requests get 403 and mutate nothing."""
    from urllib.error import HTTPError
    from urllib.request import Request, urlopen
    from horovod_tpu.runner import job_secret

    key = job_secret.make_secret_key()
    monkeypatch.setenv(job_secret.ENV, key)
    server = RendezvousServer()
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port)   # signs from env
        client.put("s", "k", b"v")
        assert client.get("s", "k") == b"v"

        # Unsigned PUT: rejected, store untouched.
        with pytest.raises(HTTPError) as e:
            urlopen(Request(f"http://127.0.0.1:{port}/s/evil",
                            data=b"x", method="PUT"), timeout=5)
        assert e.value.code == 403
        assert server.kvstore.get("s", "evil") is None

        # Unsigned GET: no data leak.
        with pytest.raises(HTTPError) as e:
            urlopen(f"http://127.0.0.1:{port}/s/k", timeout=5)
        assert e.value.code == 403

        # Wrong key: rejected.
        bad = RendezvousClient("127.0.0.1", port,
                               secret=job_secret.make_secret_key())
        with pytest.raises(HTTPError) as e:
            bad.put("s", "k2", b"x")
        assert e.value.code == 403
        assert server.kvstore.get("s", "k2") is None

        # Replay protection: a correctly-signed request with a stale
        # timestamp is rejected.
        import time
        ts = repr(time.time() - 2 * job_secret.MAX_SKEW_S)
        req = Request(f"http://127.0.0.1:{port}/s/k", method="GET")
        req.add_header(job_secret.TS_HEADER, ts)
        req.add_header(job_secret.HEADER,
                       job_secret.sign(key, "GET", "/s/k", b"", ts))
        with pytest.raises(HTTPError) as e:
            urlopen(req, timeout=5)
        assert e.value.code == 403

        # Malformed (non-ASCII) signature: clean 403, not a handler
        # traceback.
        req = Request(f"http://127.0.0.1:{port}/s/k", method="GET")
        req.add_header(job_secret.TS_HEADER, repr(time.time()))
        req.add_header(job_secret.HEADER, "café")
        with pytest.raises(HTTPError) as e:
            urlopen(req, timeout=5)
        assert e.value.code == 403

        # Anti-replay: a byte-identical resend of a correctly-signed
        # PUT (captured on the wire / departed elastic worker) is
        # rejected by the server-side signature cache even though the
        # HMAC and timestamp still verify.
        ts = repr(time.time())
        sig = job_secret.sign(key, "PUT", "/s/replayed", b"v1", ts)

        def signed_put():
            r = Request(f"http://127.0.0.1:{port}/s/replayed",
                        data=b"v1", method="PUT")
            r.add_header(job_secret.TS_HEADER, ts)
            r.add_header(job_secret.HEADER, sig)
            return urlopen(r, timeout=5)

        with signed_put():
            pass
        assert server.kvstore.get("s", "replayed") == b"v1"
        with pytest.raises(HTTPError) as e:
            signed_put()
        assert e.value.code == 403

        # PUT body gating: without a plausible signature header set,
        # the body is never read (403 precedes the upload) and an
        # over-cap Content-Length is a 400 outright.
        from horovod_tpu.runner import http_server as hs
        big = Request(f"http://127.0.0.1:{port}/s/huge", data=b"x",
                      method="PUT")
        big.add_header("Content-Length",
                       str(hs.MAX_BODY_BYTES + 1))
        with pytest.raises(HTTPError) as e:
            urlopen(big, timeout=5)
        assert e.value.code == 400
    finally:
        server.stop()


def test_rendezvous_open_without_secret(monkeypatch):
    """No job secret (direct construction, e.g. unit tests) keeps the
    server open to unsigned requests."""
    from horovod_tpu.runner import job_secret
    monkeypatch.delenv(job_secret.ENV, raising=False)
    server = RendezvousServer()
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port, secret="")
        client.put("s", "k", b"v")
        assert client.get("s", "k") == b"v"
    finally:
        server.stop()


def test_job_secret_isolation(monkeypatch):
    """Each launch mints its own key unless the caller supplies one —
    two jobs from one driver process must not share secrets."""
    from horovod_tpu.runner import job_secret
    monkeypatch.delenv(job_secret.ENV, raising=False)
    a, b = job_secret.for_job(None), job_secret.for_job(None)
    assert a != b
    assert job_secret.for_job({job_secret.ENV: "pinned"}) == "pinned"
    monkeypatch.setenv(job_secret.ENV, "from-env")
    assert job_secret.for_job(None) == "from-env"


def test_secret_transport_keeps_key_off_argv():
    """Local workers get the key via the subprocess env; the remote
    wrapper reads it from stdin — in neither case does it appear in
    the command string (argv is world-readable via /proc)."""
    import subprocess
    from horovod_tpu.runner.tpu_run import secret_transport

    cmd, env, stdin = secret_transport("echo hi", "SECRET123",
                                       local=True)
    assert cmd == "echo hi" and stdin is None
    assert env["HOROVOD_SECRET_KEY"] == "SECRET123"

    cmd, env, stdin = secret_transport(
        'echo "got:$HOROVOD_SECRET_KEY"', "SECRET123", local=False)
    assert "SECRET123" not in cmd
    assert env is None and stdin == b"SECRET123\n"
    # The wrapper really delivers the key through a shell's stdin
    # (stand-in for the far side of the ssh channel).
    out = subprocess.run(cmd, shell=True, input=stdin,
                         capture_output=True, timeout=30)
    assert b"got:SECRET123" in out.stdout, out
