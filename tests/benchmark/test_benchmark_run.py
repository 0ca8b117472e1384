"""The command end to end: the parent refuses to pass without a TPU, and
both trainers run a tiny cell on the CPU when a test steers them
there."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from benchmark_tiny import REPO, make_root, spec

ENV = dict(os.environ, JAX_PLATFORMS="cpu", HOROVOD_TPU_FORCE_CPU="1",
           PYTHONPATH=REPO)
ENV.pop("XLA_FLAGS", None)   # one CPU device a process, as one chip


def _last_json(text):
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return obj
    return None


def test_parent_refuses_to_pass_without_a_tpu():
    cell = spec.load_manifest()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         cell, "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "needs a tpu device" in done.stdout
    assert _last_json(done.stdout) is None


def test_parent_stays_off_jax_and_fixes_the_cache_path():
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from benchmarks import run
        assert "jax" not in sys.modules, "the parent imported jax"
        env = run.child_env({})
        print(env["JAX_COMPILATION_CACHE_DIR"], env["PYTHONPATH"])
        env = run.child_env({"JAX_COMPILATION_CACHE_DIR": "/given"})
        print(env["JAX_COMPILATION_CACHE_DIR"])
    """ % REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    assert out == [os.path.join(REPO, ".jax_cache"), REPO, "/given"]


@pytest.mark.parametrize("family,trace,chips", [
    ("bert", 0, 1), ("gpt", 0, 1), ("bert", 1, 1), ("bert", 0, 4)])
def test_ingraph_trainer_on_a_tiny_cell(tmp_path, family, trace, chips):
    from benchmarks.trainers import ingraph
    name = make_root(str(tmp_path), family, "ingraph", chips=chips,
                     mesh={"dp": chips})
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", "3", "--seconds", "0.5", "--trace",
         str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(run["losses"]) >= 8
    assert result["device"]["platform"] == "cpu"
    assert run["window_compiles"] == 0   # the in-graph loop is strict
    metrics = result["metrics"]
    if trace:
        # No device plane in a CPU trace: the trace's readers find
        # nothing and are left out; the clocks and counters are there.
        assert {"init_s", "compile_s", "programs_compiled"} <= set(metrics)
        assert "device_idle_share" not in metrics
        assert "breakdown" not in result
    else:
        assert run["steps"] % 10 == 0 and run["window_s"] >= 0.5
        assert metrics["samples_per_s_chip"]["value"] == pytest.approx(
            8 * run["steps"] / run["window_s"])   # 8 a chip, per chip
        assert run["n_devices_used"] == chips
        assert metrics["setup_s"]["value"] > 0
        assert "mfu" not in metrics   # no utilization off the chip


def test_same_seed_same_inputs_other_seed_other_inputs(tmp_path):
    from benchmarks.trainers import common
    name = make_root(str(tmp_path), "bert", "ingraph")
    cell = spec.Cell(name, str(tmp_path))
    family = spec.load_family(cell.config)
    a = common.batch_pool(family, cell, 5)
    b = common.batch_pool(family, cell, 5)
    c = common.batch_pool(family, cell, 6)
    other_rank = common.batch_pool(family, cell, 5, rank=1)
    assert len(a) == cell.traffic["pool_batches"]
    assert all((x["input_ids"] == y["input_ids"]).all()
               for x, y in zip(a, b))
    assert (a[0]["input_ids"] != c[0]["input_ids"]).any()
    assert (a[0]["input_ids"] != other_rank[0]["input_ids"]).any()
    assert 0.05 < a[0]["mask"].mean() < 0.3


@pytest.mark.multiproc
def test_eager_trainer_on_a_tiny_cell_two_processes(tmp_path):
    name = make_root(str(tmp_path), "bert", "eager", processes=2)
    script = tmp_path / "tiny_eager.py"
    script.write_text(textwrap.dedent("""
        import sys
        from benchmarks.trainers import eager
        eager.main(sys.argv[1:], platform="cpu", root=%r)
    """ % str(tmp_path)))
    results = {}
    for trace in (0, 1):
        out = tmp_path / ("out%d" % trace)
        out.mkdir()
        done = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
             sys.executable, str(script), "--workload", name, "--seed", "2",
             "--seconds", "1", "--trace", str(trace), "--t0",
             repr(time.time()), "--out", str(out)],
            env=ENV, cwd=REPO, capture_output=True, text=True, timeout=400)
        assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
        results[trace] = (json.loads((out / "result.json").read_text()),
                          json.loads((out / "run.json").read_text()))
    result, run = results[0]
    assert result["correct"] is True and result["device"]["count"] == 2
    assert run["steps"] % 5 == 0 and run["steps"] >= 5   # whole groups
    assert set(result["metrics"]) == {"samples_per_s_chip", "setup_s"}
    result, run = results[1]
    assert result["correct"] is True
    assert len(run["exchange_s"]) == run["steps"] == len(run["step_s"])
    assert len(run["local_step_s"]) == 2
    assert run["responses_dispatched"] >= run["steps"]
