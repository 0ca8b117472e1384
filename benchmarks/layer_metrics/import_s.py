"""Seconds rank 0 spent importing the program before the window opened:
what the ``hvd/import`` spans cover, overlaps counted once (a module
imported inside another's import is inside it).  ``horovod_tpu`` itself
is imported before ``hvd.init()`` and lies in ``init_s``; the modules
the package does not import (``horovod_tpu.training``, the models) come
after it, and their part goes on the information line."""

from benchmarks.layer_metrics import _program, _setup

LAYER = "Launch and start-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    at = _setup.instants(run)
    if at is None:
        return None
    spans = _setup.named(run, _setup.IMPORT, at[0], at[2])
    if not any("module" in s["args"] for s in spans):
        return None
    print("bench: imports before the window %s; after hvd.init() %.3f s"
          % ([[s["args"].get("module"), round(s["end"] - s["start"], 3)]
              for s in spans],
             _program.covered_s(s for s in spans if s["start"] >= at[1])),
          flush=True)
    return _program.covered_s(spans)
