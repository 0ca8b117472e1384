"""One place for JAX's persistent compilation cache.

A BERT-large step takes minutes to compile, so what a second phase or
a second process costs is decided by whether it finds the first one's
cache.  The cache's path is part of its key: every process of a run
must come to the same directory, and that directory must not move
between runs.
"""

import os

from . import env as env_mod

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(env=os.environ) -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when
    ``env`` sets it, else ``<checkout>/.jax_cache``."""
    return env.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Where the variable is set JAX reads it by itself and
    no directory is set in code."""
    where = cache_dir()
    if not env_mod.env_str_opt(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", where)
    return where
