"""Seconds rank 0 spent tracing its programs to jaxprs and lowering them
to MLIR before the window opened: the ``hvd/compile/trace`` and
``/lower`` spans, overlaps counted once.  The persistent cache saves
none of it."""

from benchmarks.layer_metrics import _program

LAYER = "Compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    spans = _program.before_window(run, ("trace", "lower"))
    if spans is None:
        return None
    print("bench: traced and lowered before the window: %d spans, the longest %s"
          % (len(spans), _program.longest(spans)), flush=True)
    return _program.covered_s(spans)
