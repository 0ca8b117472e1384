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

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["HOROVOD_TPU_FORCE_CPU"] = "1"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


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
