"""Backend compilations of one second or more in rank 0's process, from
JAX's ``/jax/core/compile/backend_compile_duration`` events that no
``/jax/compilation_cache/cache_hits`` event preceded.  JAX stores no
quicker compile, so those do not count; 0 on a warm run is the goal."""

LAYER = "Compile"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run: dict):
    return run.get("programs_compiled")
