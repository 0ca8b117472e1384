"""Seconds rank 0 spent loading compiled programs from JAX's persistent
cache before the window opened: the ``hvd/compile/cache_load`` spans."""

from benchmarks.layer_metrics import _program

LAYER = "Compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    spans = _program.before_window(run, ("cache_load",))
    return None if spans is None else sum(s["end"] - s["start"] for s in spans)
