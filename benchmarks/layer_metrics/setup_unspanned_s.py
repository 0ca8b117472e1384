"""Seconds between ``hvd.init()`` returning and the window in which no
cold span of the program was open on rank 0: the interval less what the
``hvd/`` spans cut to it cover, so that ``init_s`` + covered + this is
``setup_s``.  The benchmark's own work lies here by construction (the
reference check's executions, the warm-up steps after the first, the
batch pool, its imports): ``setup_parts`` and the longest stretches are
printed beside it."""

import json

from benchmarks.layer_metrics import _program, _setup

LAYER = "Launch and start-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    spans = _setup.after_init(run)
    if spans is None:
        return None
    covered = _program.covered_s(spans)
    unspanned = run["setup_s"] - run["init_s"] - covered
    print("bench: setup_s %.3f = init_s %.3f + %.3f in the program's spans "
          "+ %.3f in none; setup_parts %s; the longest stretches in none, "
          "by the span before: %s"
          % (run["setup_s"], run["init_s"], covered, unspanned,
             json.dumps(run.get("setup_parts")),
             _program.uncovered(run, n=8)), flush=True)
    return unspanned
