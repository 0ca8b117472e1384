"""Test configuration: force the CPU platform with 8 virtual devices.

Mirrors the reference's test strategy of standing in multi-process
localhost runs for real clusters (SURVEY §4): here an 8-device virtual
CPU mesh stands in for a TPU slice for in-graph collective tests, and
subprocess workers stand in for multi-host runs for control-plane tests.

These are CPU tests wherever they run.  The tier-1 command sets
``JAX_PLATFORMS=cpu``, which JAX honours; the ``jax.config.update``
below says the same for a bare ``pytest`` on a machine with a chip,
where this process would otherwise open the chip and hold it against
every worker it starts.
"""

import faulthandler
import os
import signal
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["HOROVOD_TPU_FORCE_CPU"] = "1"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

# Every test's own limit.  At TEST_LIMIT_S (above the 240 s that the
# longest world in tests/ may state) SIGALRM raises in the test, so
# that it fails under its own name and its ``finally`` blocks and
# fixtures still kill what it started; and again every REPEAT_S, for
# a teardown that hangs in its turn.  A main thread parked in native
# code returns to Python only when its call does, and no signal ends
# that: at HARD_LIMIT_S faulthandler's watchdog thread ends the
# process, pytest-xdist reports the test as failed with its worker,
# starts another and goes on with the file.  Both write every
# thread's stack to the run's own stderr first.
TEST_LIMIT_S = 300.0
REPEAT_S = 30.0
HARD_LIMIT_S = 360.0

_stderr_fd = pytest.StashKey[int]()


def pytest_configure(config):
    # fd 2 as it is while pytest captures nothing: inside a test it is
    # the capture's file, which dies with the process.
    config.stash[_stderr_fd] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_stderr_fd])


@pytest.fixture(autouse=True)
def own_limit(request):
    """faulthandler has one watchdog a process and this fixture owns
    it: no ``faulthandler_timeout`` in pytest.ini, which arms and
    cancels the same one around each test."""
    stderr = request.config.stash[_stderr_fd]

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=stderr, all_threads=True)
        pytest.fail(f"{request.node.nodeid} exceeded {TEST_LIMIT_S:g} s; "
                    "every thread's stack is on stderr")

    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S, REPEAT_S)
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True, file=stderr)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def no_worker_outlives_its_test(tmp_path):
    """For tests that call a launcher in this process.  It waits for
    its workers as long as they run; when the test's own limit ends
    that wait, its threads are daemons and the workers are nobody's.
    Their scripts, or a path in their environment, are under
    ``tmp_path``: whoever names it is killed."""
    from multiproc import kill_with
    yield
    kill_with(str(tmp_path))


@pytest.fixture
def lock_witness():
    """Arm the runtime lock-order witness (docs/static_analysis.md)
    for the duration of a test and FAIL it on any recorded cycle.
    Used by the chaos smoke and the replay e2e suite — the two lanes
    that exercise the full multi-threaded control plane in-process."""
    from horovod_tpu.common import lockwitness as lw
    lw.reset()
    lw.enable()
    try:
        yield lw
        lw.assert_no_cycles()
    finally:
        lw.disable()
        lw.reset()


@pytest.fixture
def hvd_single():
    """Initialized single-process horovod_tpu, clean shutdown after."""
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture
def cpu_mesh8():
    from horovod_tpu.parallel import build_mesh
    return build_mesh({"dp": 8})
