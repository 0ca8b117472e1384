"""Helper to run test bodies across N local worker processes.

Mirrors the reference's test strategy of standing in N localhost
processes for a cluster (SURVEY §4: every parallel test runs under
``mpirun -np 2 -H localhost:2``).  Here the launcher env contract is set
manually and workers are plain subprocesses; the controller rides TCP
and the data plane rides gloo cross-process CPU collectives — the same
code path as a TPU pod minus the hardware.
"""

import itertools
import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_IMPORTS = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
"""

_INIT = """
hvd.init()
RANK = hvd.rank()
SIZE = hvd.size()
"""


# What a killed process may take to be reaped.
_REAP_S = 10.0


def free_port() -> int:
    return free_ports(1)[0]


# A world keeps its two ports for its life, and between two of its
# incarnations (``hvd.shutdown()``, ``hvd.init()``) nobody holds them.
# A port the kernel chose (``bind`` to 0) lies in its ephemeral range,
# where any ``connect`` of any process and any other worker's
# ``bind`` to 0 may be given the same number in that gap.  So each
# pytest-xdist worker hands out ports of a range of its own below that
# range (32768 and up), in turn, so that a world does not get the
# ports of the one before it either; the turn starts at the pid, for
# two pytest processes that are not workers of one run.
_PORTS_FROM, _PORTS_A_WORKER, _WORKERS = 20000, 1500, 8
_turn = itertools.count(os.getpid())


def free_ports(n: int) -> List[int]:
    """``n`` distinct ports of this worker's range on which nothing is
    bound."""
    worker = int(os.environ.get("PYTEST_XDIST_WORKER", "gw0")[2:])
    first = _PORTS_FROM + worker % _WORKERS * _PORTS_A_WORKER
    ports = []
    while len(ports) < n:
        port = first + next(_turn) % _PORTS_A_WORKER
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        ports.append(port)
    return ports


def run_workers(body: str, nproc: int = 2, timeout: float = 180.0,
                extra_env: Optional[dict] = None,
                per_rank_env=None,
                before_init: str = "") -> List[Tuple[int, str]]:
    """Run ``body`` (dedented python source, sees RANK/SIZE/np/hvd/jax)
    in ``nproc`` worker processes.  Returns [(returncode, output)].
    ``before_init`` runs after the imports and before ``hvd.init()``.

    ``timeout`` is the limit of the world, not of each rank: when it
    passes, every rank still alive is killed with whatever it started
    and comes back as ``(-9, output so far)``.

    ``per_rank_env(rank) -> dict`` overrides the env contract per rank
    (e.g. to simulate a two-tier host topology on localhost).
    """
    coord_port, ctrl_port = free_ports(2)
    code = _IMPORTS + textwrap.dedent(before_init) + _INIT + \
        textwrap.dedent(body)
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(nproc),
            "HOROVOD_LOCAL_RANK": str(rank),
            "HOROVOD_LOCAL_SIZE": str(nproc),
            "HOROVOD_CROSS_RANK": "0",
            "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_TPU_COORDINATOR": f"127.0.0.1:{coord_port}",
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{ctrl_port}",
            "HOROVOD_TPU_FORCE_CPU": "1",
            "PYTHONPATH": REPO,
        })
        supplied = dict(extra_env or {})
        if per_rank_env:
            supplied.update({k: str(v)
                             for k, v in per_rank_env(rank).items()})
        # Steady-state replay OFF by default in worker tests: these
        # suites are the CH/CB negotiation-protocol tests, and replay
        # (round 6) legitimately stops steady-state wire traffic their
        # frame-count assertions depend on.  Negotiation remains the
        # warm-up/fallback path so this coverage stays load-bearing;
        # replay has its own opt-in suite
        # (tests/test_steady_state_replay.py passes the env
        # explicitly) and the chaos kill drill.
        supplied.setdefault("HOROVOD_STEADY_STATE_REPLAY", "0")
        # Liveness ON by default with tight (test-scale) values: a
        # wedged or killed worker surfaces within seconds instead of
        # hanging a suite to its subprocess timeout.  HB frames ride
        # their own stats key/metric label, so the legacy CH/RQ
        # frame-count assertions are unaffected.  Skipped when the
        # test pins the native coordinator: the self-healing channel
        # is Python-coordinator-only (HB frames would kill native
        # links), and strict-native + liveness is a config error by
        # design.  Known tradeoff: this also removes AUTO-native
        # selection from the non-pinned suites — native-coordinator
        # protocol coverage now lives entirely in the suites that set
        # HOROVOD_TPU_NATIVE=1 (test_native_coordinator and the [1]
        # variants of ring/response-cache/replay tests).
        if supplied.get("HOROVOD_TPU_NATIVE", "").strip().lower() \
                not in ("1", "true", "on", "yes"):
            supplied.setdefault("HOROVOD_LIVENESS_INTERVAL", "3")
            supplied.setdefault("HOROVOD_LIVENESS_TIMEOUT", "15")
            supplied.setdefault("HOROVOD_RECONNECT_GRACE", "10")
        # A world that cannot form says so within a minute, in JAX's
        # and the controller's own words; it is not killed at the
        # world's limit with nothing said.
        supplied.setdefault("HOROVOD_START_TIMEOUT", "60")
        env.update(supplied)
        # Workers default to 1 CPU device: scrub the conftest's
        # 8-device XLA_FLAGS unless the test supplied its own.
        if "XLA_FLAGS" not in supplied:
            env.pop("XLA_FLAGS", None)
        # A session of its own, so that the rank's process group can
        # be killed with every process the rank started; output to a
        # file, which no grandchild can hold open against the reader
        # and no rank can fill while another is waited for.
        out = tempfile.TemporaryFile()
        procs.append((subprocess.Popen(
            [sys.executable, "-c", code], env=env, stdout=out,
            stderr=subprocess.STDOUT, start_new_session=True), out))
    deadline = time.monotonic() + timeout
    try:
        while time.monotonic() < deadline and not all(
                _exited(p) for p, _ in procs):
            time.sleep(0.02)
    finally:
        # On the way out of an exception too (the test's own limit):
        # no process of the world outlives the call.  Rank 0 last: it
        # hosts the world's services, and a rank that sees them die
        # ends itself with exit code 1 before its own kill arrives.
        for p, _ in reversed(procs):
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    results = []
    for p, out in procs:
        rc = p.wait(_REAP_S)
        out.seek(0)
        results.append((rc, out.read().decode(errors="replace")))
        out.close()
    return results


def _exited(p: subprocess.Popen) -> bool:
    """Whether ``p`` has exited, WITHOUT reaping it: until it is
    reaped its pid, which names its process group, is nobody else's,
    so the kill of the group cannot fall on a stranger."""
    return os.waitid(os.P_PID, p.pid,
                     os.WEXITED | os.WNOWAIT | os.WNOHANG) is not None


def alive_with(token: str) -> List[int]:
    """Pids of the live processes whose command line or environment
    holds ``token``: a path under a test's ``tmp_path`` marks whatever
    the test's launcher started, at any depth."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            for what in ("cmdline", "environ"):
                with open(f"/proc/{pid}/{what}", "rb") as f:
                    if token.encode() in f.read():
                        found.append(int(pid))
                        break
        except OSError:     # gone meanwhile, or not ours to read
            pass
    return found


def kill_with(token: str) -> None:
    for pid in alive_with(token):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def assert_all_ok(results: List[Tuple[int, str]]):
    for i, (rc, out) in enumerate(results):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-3000:]}"
