"""Helper to run test bodies across N local worker processes.

Mirrors the reference's test strategy of standing in N localhost
processes for a cluster (SURVEY §4: every parallel test runs under
``mpirun -np 2 -H localhost:2``).  Here the launcher env contract is set
manually and workers are plain subprocesses; the controller rides TCP
and the data plane rides gloo cross-process CPU collectives — the same
code path as a TPU pod minus the hardware.
"""

import os
import socket
import subprocess
import sys
import textwrap
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


def free_port() -> int:
    return free_ports(1)[0]


def free_ports(n: int) -> List[int]:
    """Allocate n distinct free ports, holding all sockets open until
    every port is chosen (sequential bind/close can hand out the same
    port twice — the jax coordinator and the controller server would
    then race for it)."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def run_workers(body: str, nproc: int = 2, timeout: float = 180.0,
                extra_env: Optional[dict] = None,
                per_rank_env=None,
                before_init: str = "") -> List[Tuple[int, str]]:
    """Run ``body`` (dedented python source, sees RANK/SIZE/np/hvd/jax)
    in ``nproc`` worker processes.  Returns [(returncode, output)].
    ``before_init`` runs after the imports and before ``hvd.init()``.

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
        env.update(supplied)
        # Workers default to 1 CPU device: scrub the conftest's
        # 8-device XLA_FLAGS unless the test supplied its own.
        if "XLA_FLAGS" not in supplied:
            env.pop("XLA_FLAGS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    results = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            results.append((-9, out.decode(errors="replace")))
            continue
        results.append((p.returncode, out.decode(errors="replace")))
    return results


def assert_all_ok(results: List[Tuple[int, str]]):
    for i, (rc, out) in enumerate(results):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-3000:]}"
