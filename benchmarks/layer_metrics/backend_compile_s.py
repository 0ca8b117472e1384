"""Seconds rank 0 spent in XLA compiling programs the persistent cache
did not hold, of any length, before the window opened: the
``hvd/compile/backend_compile`` spans.  Small on a warm run."""

from benchmarks.layer_metrics import _program

LAYER = "Compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    spans = _program.before_window(run, ("backend_compile",))
    return None if spans is None else sum(s["end"] - s["start"] for s in spans)
