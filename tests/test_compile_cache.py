"""The persistent compilation cache can be placed from outside, and a
second process finds what the first one compiled."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Enables the cache through the package's helper, compiles one tiny
# step, and reports JAX's own cache events and configured directory.
CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from jax import monitoring
    from horovod_tpu.common import compile_cache
    events = []
    monitoring.register_event_listener(lambda e, **kw: events.append(e))
    where = compile_cache.enable()
    jax.jit(lambda x: jnp.tanh(x @ x).sum())(jnp.ones((64, 64)))
    hits = sum(e == "/jax/compilation_cache/cache_hits" for e in events)
    print("CACHE", where, jax.config.jax_compilation_cache_dir, hits)
""")


def _run_child(env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         check=True, capture_output=True, text=True,
                         timeout=120).stdout
    _, where, configured, hits = out.strip().splitlines()[-1].split()
    return where, configured, int(hits)


def test_cache_dir_left_to_the_environment(tmp_path, monkeypatch):
    """Variable set: the helper sets no directory in code; JAX reads
    the variable itself and a process writes its entries there."""
    from horovod_tpu.common import compile_cache
    calls = []
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    monkeypatch.setattr("jax.config.update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []

    where, configured, _ = _run_child(str(tmp_path))
    assert where == configured == str(tmp_path)
    assert os.listdir(tmp_path)


def test_cache_dir_defaults_to_the_checkout():
    """Variable unset: the fixed <checkout>/.jax_cache, never a
    temporary or per-process path; a second process compiling the same
    step reports a cache hit."""
    from horovod_tpu.common import compile_cache
    default = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir({}) == default
    assert compile_cache.cache_dir({compile_cache.ENV: "/x"}) == "/x"

    where, configured, _ = _run_child()
    assert where == configured == default
    assert _run_child()[2] >= 1
