"""End-to-end launcher integration tests on localhost.

Mirrors the reference's test/integration/test_static_run.py: real
worker processes through the real launcher, 2-process localhost run
standing in for a cluster (SURVEY §4).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_SCRIPT = textwrap.dedent("""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    out = hvd.allreduce(np.array([1.0, 2.0]) * (hvd.rank() + 1),
                        name="t", op=hvd.Sum)
    expected = np.array([1.0, 2.0]) * sum(
        r + 1 for r in range(hvd.size()))
    assert np.allclose(out, expected), (out, expected)
    print(f"OK rank={hvd.rank()} size={hvd.size()}")
    hvd.shutdown()
""")


def _worker_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_TPU_FORCE_CPU"] = "1"
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_RANK", None)
    return env


pytestmark = pytest.mark.usefixtures("no_worker_outlives_its_test")


def test_launch_static_two_procs(tmp_path):
    from horovod_tpu.runner.tpu_run import launch_static
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    outdir = tmp_path / "logs"
    codes = launch_static(
        [sys.executable, str(script)], "localhost:2", 2,
        env=_worker_env(), output_filename=str(outdir), verbose=1)
    assert codes == {0: 0, 1: 0}
    # Per-rank capture files exist and contain the OK line
    # (reference behavior: gloo_run.py:150-163).
    for rank in (0, 1):
        stdout = (outdir / f"rank.{rank}" / "stdout").read_text()
        assert f"OK rank={rank} size=2" in stdout


def test_launcher_parent_stays_off_jax(tmp_path):
    """A chip belongs to one process at a time: the launcher imports
    JAX with the package but must never initialise a backend, or its
    workers could not open the chip.  It also hands every worker the
    compile cache directory and, to several local slots, a chip each."""
    script = tmp_path / "env.py"
    script.write_text(
        "import os\n"
        "print('CHIP', os.environ.get('TPU_VISIBLE_CHIPS'),"
        " os.environ['JAX_COMPILATION_CACHE_DIR'])\n")
    parent = textwrap.dedent("""
        import sys
        from horovod_tpu.runner.tpu_run import launch_static
        launch_static([sys.executable, %r], "localhost:4", 4,
                      output_filename=%r)
        from jax._src import xla_bridge
        assert not xla_bridge._backends, xla_bridge._backends
    """ % (str(script), str(tmp_path / "logs")))
    env = _worker_env()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    subprocess.run([sys.executable, "-c", parent], env=env, check=True,
                   timeout=120)
    for rank in range(4):
        out = (tmp_path / "logs" / f"rank.{rank}" / "stdout").read_text()
        assert out.split(":", 1)[1].split() == \
            ["CHIP", str(rank), str(tmp_path / "cache")]


def test_launch_static_failure_propagates(tmp_path):
    from horovod_tpu.runner.tpu_run import launch_static
    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(3)")
    with pytest.raises(RuntimeError, match="non-zero exit"):
        launch_static([sys.executable, str(script)], "localhost:2", 2,
                      env=_worker_env())


def test_programmatic_run():
    """hvd.run()-style API returns per-rank results ordered by rank
    (reference: runner/__init__.py:91-206)."""
    from horovod_tpu.runner import run

    def fn(offset):
        import jax
        jax.config.update("jax_platforms", "cpu")
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank() + offset
        hvd.shutdown()
        return r

    results = run(fn, args=(100,), np=2, env=_worker_env())
    assert results == [100, 101]


def test_elastic_tf2_resnet50_example_static(tmp_path):
    """The elastic TF2 example (a BASELINE config) must run end-to-end
    through the real launcher on 2 localhost workers (tiny model)."""
    pytest.importorskip("tensorflow")
    from horovod_tpu.runner.tpu_run import launch_static
    script = os.path.join(REPO, "examples", "elastic", "tensorflow2",
                          "tensorflow2_resnet50_elastic.py")
    outdir = tmp_path / "logs"
    codes = launch_static(
        [sys.executable, script, "--model", "simple",
         "--image-size", "32", "--batch-size", "4",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2"],
        "localhost:2", 2, env=_worker_env(),
        output_filename=str(outdir), verbose=1, start_timeout=240)
    assert codes == {0: 0, 1: 0}
    stdout = (outdir / "rank.0" / "stdout").read_text()
    assert "img/sec per worker" in stdout
    assert "Total img/sec on 2 workers" in stdout
