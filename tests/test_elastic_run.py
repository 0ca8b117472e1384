"""End-to-end elastic test: real worker processes, scripted discovery
churn (the reference's integration technique — a discovery script whose
output changes mid-run, test/integration/elastic_common.py:34-65).

World grows localhost:2 → localhost:3 while training runs; surviving
workers re-form the jax.distributed world in-process; the new worker
syncs committed state; training continues with size 3.
"""

import os
import re
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


WORKER_SCRIPT = """
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd
import horovod_tpu.jax as hj
from horovod_tpu.jax.elastic import JaxState, run

hvd.init()
state = JaxState(epoch=0)
STOP = os.environ["TEST_STOP_FILE"]

@run
def train(state):
    while True:
        # The stop decision must be COLLECTIVE: ranks polling the
        # file independently can disagree by one epoch (one rank
        # exits, the rest wedge on its missing contribution).
        stop = np.asarray(hj.allreduce(
            np.asarray([float(os.path.exists(STOP))], np.float32),
            op=hvd.Sum, name="stopflag"))
        if stop[0] > 0:
            return state.epoch
        val = np.asarray(hj.allreduce(
            np.ones(4, np.float32), op=hvd.Sum,
            name=f"t{state.epoch}"))
        assert val[0] == hvd.size(), (val, hvd.size())
        print(f"EPOCH {state.epoch} rank={hvd.rank()} "
              f"size={hvd.size()}", flush=True)
        state.epoch += 1
        state.commit()
        time.sleep(0.05)

train(state)
print(f"DONE rank={hvd.rank()} epoch={state.epoch}", flush=True)
"""


class _ElasticRun:
    """``launch_elastic`` on a thread of its own, over a discovery
    script that prints ``hosts_file``, with ONE deadline for the whole
    run: every wait takes what is left of it."""

    LIMIT_S = 240.0

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.hosts_file = tmp_path / "hosts.txt"
        self.stop_file = tmp_path / "stop"
        self.outdir = tmp_path / "out"
        self.result = {}

    def start(self, worker_src, hosts, extra_worker_env, **launch):
        from horovod_tpu.runner.elastic.discovery import \
            HostDiscoveryScript
        from horovod_tpu.runner.elastic_run import launch_elastic

        self.hosts_file.write_text(hosts)
        script = self.tmp_path / "discover.sh"
        script.write_text(f"#!/bin/sh\ncat {self.hosts_file}\n")
        script.chmod(0o755)
        worker_py = self.tmp_path / "worker.py"
        worker_py.write_text(worker_src)

        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

        def run_launcher():
            try:
                self.result["codes"] = launch_elastic(
                    [sys.executable, str(worker_py)],
                    discovery=HostDiscoveryScript(str(script), 1),
                    output_filename=str(self.outdir),
                    env=env,
                    extra_worker_env={
                        "HOROVOD_TPU_FORCE_CPU": "1",
                        "TEST_STOP_FILE": str(self.stop_file),
                        **extra_worker_env},
                    **launch)
            except Exception as e:   # surfaced in the main thread
                self.result["error"] = e

        self.deadline = time.monotonic() + self.LIMIT_S
        self.thread = threading.Thread(target=run_launcher, daemon=True)
        self.thread.start()

    def logs(self):
        text = ""
        for root, _, files in os.walk(self.outdir):
            for f in files:
                with open(os.path.join(root, f),
                          errors="replace") as fh:
                    text += fh.read()
        return text

    def wait_for(self, pattern):
        while time.monotonic() < self.deadline:
            if re.search(pattern, self.logs()):
                return
            if not self.thread.is_alive():
                raise AssertionError(
                    f"launcher exited early: {self.result}\n"
                    f"logs:\n{self.logs()[-3000:]}")
            time.sleep(0.5)
        raise AssertionError(
            f"pattern {pattern!r} did not appear within "
            f"{self.LIMIT_S:g} s of the start; logs:\n"
            f"{self.logs()[-3000:]}")

    def finish(self):
        """Stop; everyone exits cleanly.  Returns the launcher's exit
        codes and the logs."""
        self.stop_file.write_text("")
        self.thread.join(
            timeout=max(0.0, self.deadline - time.monotonic()))
        assert not self.thread.is_alive(), "launcher did not finish"
        assert "error" not in self.result, self.result.get("error")
        return self.result["codes"], self.logs()


@pytest.fixture
def elastic(tmp_path, no_worker_outlives_its_test):
    """After a wait that failed the launcher's thread is a daemon: the
    discovery script then offers it no host to start another worker
    on, and the workers it has, with this run's stop file in their
    environment, are killed."""
    run = _ElasticRun(tmp_path)
    yield run
    run.hosts_file.write_text("")


def test_elastic_world_grows(elastic):
    elastic.start(WORKER_SCRIPT, "localhost:2\n",
                  {"HOROVOD_START_TIMEOUT": "60"},
                  np=2, min_np=2, max_np=3, elastic_timeout=60)
    # Phase 1: two workers train at size 2.
    elastic.wait_for(r"EPOCH \d+ rank=\d size=2")
    # Phase 2: a third slot appears; world re-forms at size 3.
    elastic.hosts_file.write_text("localhost:3\n")
    elastic.wait_for(r"EPOCH \d+ rank=2 size=3")
    # Phase 3: stop; everyone exits cleanly.
    codes, logs = elastic.finish()
    assert set(codes.values()) == {0}
    assert len(re.findall(r"DONE rank=\d", logs)) == 3


KILLABLE_WORKER = """
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd
import horovod_tpu.jax as hj
from horovod_tpu.jax.elastic import JaxState, run

hvd.init()
state = JaxState(epoch=0)
STOP = os.environ["TEST_STOP_FILE"]
DOOMED = os.environ["HOROVOD_HOSTNAME"] == os.environ["TEST_DOOMED_HOST"]

@run
def train(state):
    while not os.path.exists(STOP):
        if DOOMED and state.epoch >= 3:
            print("DYING", flush=True)
            os._exit(1)   # hard death mid-run, no cleanup
        val = np.asarray(hj.allreduce(
            np.ones(4, np.float32), op=hvd.Sum,
            name=f"t{state.epoch}"))
        assert val[0] == hvd.size(), (val, hvd.size())
        print(f"EPOCH {state.epoch} rank={hvd.rank()} "
              f"size={hvd.size()}", flush=True)
        state.epoch += 1
        state.commit()
        time.sleep(0.05)
    return state.epoch

train(state)
print(f"DONE rank={hvd.rank()} epoch={state.epoch} "
      f"size={hvd.size()}", flush=True)
"""


def test_elastic_worker_death_shrinks_world(elastic):
    """A worker hard-dies (os._exit, no cleanup) mid-run: the driver
    records the failure, blacklists that host, survivors unwind via
    HorovodInternalError, restore committed state, and continue at the
    smaller world size (reference: exit_schedule scenarios,
    test/integration/elastic_common.py; failure path SURVEY §5).
    Two distinct host strings (localhost / 127.0.0.1) both resolve
    locally, so blacklisting the doomed 'host' spares the survivor."""
    elastic.start(KILLABLE_WORKER, "localhost:1\n127.0.0.1:1\n",
                  {"TEST_DOOMED_HOST": "127.0.0.1",
                   "HOROVOD_START_TIMEOUT": "60"},
                  np=2, min_np=1, max_np=2, elastic_timeout=60)
    # Phase 1: both workers train at size 2; the doomed one dies.
    elastic.wait_for(r"EPOCH \d+ rank=\d size=2")
    elastic.wait_for(r"DYING")
    # Phase 2: the survivor re-forms at size 1, resuming from a
    # committed epoch >= 3 (state survived the membership change).
    elastic.wait_for(r"EPOCH [3-9]\d* rank=0 size=1")
    # Phase 3: stop; survivor exits cleanly.
    codes, logs = elastic.finish()
    m = re.search(r"DONE rank=0 epoch=(\d+) size=1", logs)
    assert m and int(m.group(1)) >= 3, logs[-2000:]
    # The dead slot's non-zero code is recorded, not fatal.
    assert any(c != 0 for c in codes.values()), codes


TWO_TIER_WORKER = """
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd
import horovod_tpu.jax as hj
from horovod_tpu.common import basics
from horovod_tpu.jax.elastic import JaxState, run

hvd.init()
state = JaxState(epoch=0)
STOP = os.environ["TEST_STOP_FILE"]
DOOMED = os.environ["HOROVOD_HOSTNAME"] == os.environ["TEST_DOOMED_HOST"]

@run
def train(state):
    while not os.path.exists(STOP):
        if DOOMED and state.epoch >= 2:
            print("DYING", flush=True)
            os._exit(1)
        val = np.asarray(hj.allreduce(
            np.ones(2, np.float32), op=hvd.Sum,
            name=f"t{state.epoch}"))
        assert val[0] == hvd.size(), (val, hvd.size())
        # The two-tier contract must hold at the CURRENT world:
        # rank = cross_rank * local_size + local_rank, and when
        # local_size > 1 the hierarchical proc mesh must re-form.
        ri = basics._state().rank_info
        assert ri.rank == ri.cross_rank * ri.local_size + \
            ri.local_rank, vars(ri)
        be = basics._state().backend
        hier = getattr(be, "fallback", be)
        if ri.local_size > 1 and ri.size > 1:
            assert hier._hier_kind == "proc", hier._hier_kind
            assert hier._hier.devices.shape == \
                (ri.cross_size, ri.local_size)
        print(f"EPOCH {state.epoch} rank={hvd.rank()} "
              f"size={hvd.size()} lr={ri.local_rank} "
              f"ls={ri.local_size} cr={ri.cross_rank} "
              f"cs={ri.cross_size}", flush=True)
        state.epoch += 1
        state.commit()
        time.sleep(0.05)
    return state.epoch

train(state)
print(f"DONE rank={hvd.rank()} epoch={state.epoch} "
      f"size={hvd.size()}", flush=True)
"""


def test_elastic_two_tier_host_loss(elastic):
    """VERDICT r3 item 6 (elastic leg): a 2-host x 2-slot world loses
    a whole 'host' mid-run; survivors re-rendezvous as 1 host x 2
    slots with the local/cross contract recomputed (cross_size 2 -> 1)
    and the hierarchical mesh re-formed over the new topology."""
    elastic.start(
        TWO_TIER_WORKER, "localhost:2\n127.0.0.1:2\n",
        {"HOROVOD_CPU_OPERATIONS": "XLA",
         # One virtual device per worker: the host tier is simulated
         # by PROCESS groups, so the conftest's 8-device XLA_FLAGS
         # must not leak in (it would flip the hierarchy to
         # device-kind).
         "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
         "TEST_DOOMED_HOST": "127.0.0.1",
         "HOROVOD_START_TIMEOUT": "90"},
        np=4, min_np=2, max_np=4, elastic_timeout=60)
    # Phase 1: 4 workers, two-tier (cross_size=2, local_size=2).
    elastic.wait_for(r"EPOCH \d+ rank=\d size=4 lr=\d ls=2 cr=\d cs=2")
    elastic.wait_for(r"DYING")
    # Phase 2: the dead host's pair is blacklisted; the surviving host
    # re-forms as one tier (size 2, cross_size 1) from committed state.
    elastic.wait_for(
        r"EPOCH [2-9]\d* rank=\d size=2 lr=\d ls=2 cr=0 cs=1")
    # Phase 3: stop; survivors exit cleanly.
    codes, logs = elastic.finish()
    assert len(re.findall(r"DONE rank=\d epoch=\d+ size=2", logs)) == 2
    assert any(c != 0 for c in codes.values()), codes


TF_GRAPH_ELASTIC_WORKER = """
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tensorflow as tf
import horovod_tpu.tensorflow as hvd

hvd.init()
assert hvd.enable_graph_collectives(), "graph collectives must enable"
STOP = os.environ["TEST_STOP_FILE"]
DOOMED = os.environ["HOROVOD_HOSTNAME"] == os.environ["TEST_DOOMED_HOST"]


def build():
    m = tf.keras.Sequential([tf.keras.layers.Input((4,)),
                             tf.keras.layers.Dense(1)])
    o = tf.optimizers.SGD(0.01)

    @tf.function
    def step(x, y):
        with tf.GradientTape() as tape:
            loss = tf.reduce_mean((m(x) - y) ** 2)
        tape = hvd.DistributedGradientTape(tape)
        g = tape.gradient(loss, m.trainable_variables)
        o.apply_gradients(zip(g, m.trainable_variables))
        return loss
    return m, o, step


def make_data():
    return tf.ones((2, 4)), tf.ones((2, 1))


m, o, step = build()
x, y = make_data()
step(x, y)   # weights exist before the first sync broadcast

state = hvd.elastic.TensorFlowKerasState(m, o, epoch=0)


def path_of(fn):
    cf = fn.get_concrete_function(tf.TensorSpec([2, 4]),
                                  tf.TensorSpec([2, 1]))
    ops = {op.type for op in cf.graph.get_operations()}
    if any("PyFunc" in t for t in ops):
        return "py_function"
    if "CollectiveReduceV2" in ops:
        return "collective_v2"
    return "local"


def on_reset():
    # HOROVOD_TF_ELASTIC_GRAPH reset the TF context: rebuild the
    # model + traced function, re-point the state snapshots.
    global m, o, step, x, y
    m, o, step = build()
    x, y = make_data()
    step(x, y)
    state.rebuild(m, o)


state.register_reset_callbacks([on_reset])


@hvd.elastic.run
def train(state):
    while not os.path.exists(STOP):
        if DOOMED and state.epoch >= 2:
            print("DYING", flush=True)
            os._exit(1)
        t0 = time.perf_counter()
        step(x, y)
        dt = (time.perf_counter() - t0) * 1e3
        print(f"EPOCH {state.epoch} rank={hvd.rank()} "
              f"size={hvd.size()} path={path_of(step)} "
              f"ms={dt:.2f}", flush=True)
        state.epoch += 1
        state.commit()
        time.sleep(0.05)
    return state.epoch


train(state)
print(f"DONE rank={hvd.rank()} epoch={state.epoch} "
      f"size={hvd.size()} path={path_of(step)}", flush=True)
"""


def test_elastic_in_graph_tf_survives_resize(elastic):
    """VERDICT r3 item 5: elastic TF2 trains through a resize WITH
    in-graph collectives on both sides of it (HOROVOD_TF_ELASTIC_GRAPH
    context-reset re-formation): 3 workers train with CollectiveReduceV2
    in the traced graph, one hard-dies, the survivors re-form at size 2
    and the retraced step still carries CollectiveReduceV2 — never
    py_function. The collective path and per-step time are in the log."""
    elastic.start(TF_GRAPH_ELASTIC_WORKER, "localhost:2\n127.0.0.1:1\n",
                  {"HOROVOD_TF_ELASTIC_GRAPH": "1",
                   "TEST_DOOMED_HOST": "127.0.0.1",
                   "HOROVOD_START_TIMEOUT": "120",
                   "TF_CPP_MIN_LOG_LEVEL": "2"},
                  np=3, min_np=2, max_np=3, elastic_timeout=90)
    # Phase 1: 3 workers on the compiled collective path.
    elastic.wait_for(r"EPOCH \d+ rank=\d size=3 path=collective_v2")
    elastic.wait_for(r"DYING")
    # Phase 2: survivors re-form at size 2, STILL in-graph.
    elastic.wait_for(r"EPOCH \d+ rank=\d size=2 path=collective_v2")
    _, logs = elastic.finish()
    assert "path=py_function" not in logs
    assert len(re.findall(
        r"DONE rank=\d epoch=\d+ size=2 path=collective_v2",
        logs)) == 2
