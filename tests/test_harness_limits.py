"""The limits of the test harness itself: a world's one deadline
(``multiproc.run_workers``), every test's own limit (the ``own_limit``
fixture of ``conftest.py``) and the deadline of a world that cannot
form (``HOROVOD_START_TIMEOUT`` handed to ``jax.distributed``)."""

import os
import re
import subprocess
import sys
import time
import uuid

from multiproc import REPO, alive_with, run_workers

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_a_wedged_world_costs_its_limit_once_and_leaves_nobody():
    """Three ranks that sleep for ever, each beside a child of its own:
    back after one ``timeout`` and not three, every rank ``-9``, what
    they printed kept, and nobody of the world alive."""
    token = f"harness-limits-{uuid.uuid4().hex}"
    started = time.monotonic()
    results = run_workers(
        "time.sleep(3600)", nproc=3, timeout=15,
        extra_env={"HARNESS_LIMITS_WORLD": token},
        before_init="""
        import subprocess, time
        child = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(3600)"])
        print("GRANDCHILD", child.pid, flush=True)
        """)
    took = time.monotonic() - started
    assert took < 25, took
    assert [rc for rc, _ in results] == [-9, -9, -9], results
    assert all(re.search(r"GRANDCHILD \d+", out) for _, out in results), \
        results
    assert alive_with(token) == []


_FOUR_TESTS = """
import signal, time

def test_sleeps_in_python():
    time.sleep(60)  # THE LINE IT SLEPT ON

def test_passes_in_the_same_worker():
    pass

def test_sleeps_where_no_signal_reaches():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(60)

def test_passes_in_the_next_worker():
    pass
"""


def test_a_test_past_its_limit_fails_by_name_and_the_run_goes_on(tmp_path):
    """``conftest.py`` with its two limits cut to seconds, over four
    tests under xdist: the sleeper fails under its own name with its
    line in the stacks and its worker lives on; the one no signal
    reaches costs its worker, stacks printed, and the run goes on."""
    with open(os.path.join(TESTS, "conftest.py")) as f:
        conftest = f.read()
    for name, short in (("TEST_LIMIT_S = 300.0", "TEST_LIMIT_S = 2.0"),
                        ("HARD_LIMIT_S = 360.0", "HARD_LIMIT_S = 6.0")):
        assert conftest.count(name) == 1, name
        conftest = conftest.replace(name, short)
    (tmp_path / "conftest.py").write_text(conftest)
    (tmp_path / "test_four.py").write_text(_FOUR_TESTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "test_four.py", "-v",
         "-p", "no:cacheprovider", "-p", "xdist", "-n", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = done.stdout + done.stderr
    assert done.returncode == 1, out
    assert re.search(r"2 failed, 2 passed", out), out
    # The limit of the first: its name, its seconds and its line.
    assert "test_four.py::test_sleeps_in_python exceeded 2 s" in out, out
    assert re.search(r'test_four\.py", line 5 in test_sleeps_in_python',
                     done.stderr), out
    assert "THE LINE IT SLEPT ON" in done.stdout, out
    assert re.search(r"\[gw0\].* PASSED test_four.py::"
                     r"test_passes_in_the_same_worker", out), out
    # The hard limit of the third: stacks, a failure, another worker.
    assert re.search(r'test_four\.py", line 12 in '
                     r"test_sleeps_where_no_signal_reaches",
                     done.stderr), out
    assert "crashed while running 'test_four.py::" \
        "test_sleeps_where_no_signal_reaches'" in out, out
    assert re.search(r"\[gw1\].* PASSED test_four.py::"
                     r"test_passes_in_the_next_worker", out), out


def test_a_world_that_cannot_form_gives_up_at_the_start_timeout():
    """Rank 1 of 2 with nobody at the coordinator's address: JAX's
    registration ends at ``HOROVOD_START_TIMEOUT``, in JAX's words, and
    not at JAX's own 300 s."""
    started = time.monotonic()
    (rc, out), = run_workers("", nproc=1, timeout=60, per_rank_env=lambda _: {
        "HOROVOD_RANK": 1, "HOROVOD_SIZE": 2,
        "HOROVOD_LOCAL_RANK": 1, "HOROVOD_LOCAL_SIZE": 2,
        "HOROVOD_START_TIMEOUT": 5})
    took = time.monotonic() - started
    assert rc not in (0, -9), out[-3000:]
    assert "DEADLINE_EXCEEDED" in out, out[-3000:]
    assert 5 < took < 40, took
