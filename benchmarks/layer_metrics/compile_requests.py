"""Compilation requests rank 0 made before the window opened, whatever
their outcome (hit, miss, uncached): the increments of
``hvd_compile_requests_total``, each placed in time by the
``cache_load`` or ``backend_compile`` span that closes its request."""

from benchmarks.layer_metrics import _program

LAYER = "Compile"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run: dict):
    spans = _program.before_window(run, ("cache_load", "backend_compile"))
    if spans is None:
        return None
    print("bench: compile requests before the window: %d spans, the longest %s"
          % (len(spans), _program.longest(spans)), flush=True)
    return len(spans)
