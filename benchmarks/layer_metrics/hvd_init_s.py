"""Seconds inside ``hvd.init()`` on rank 0: the program's own
``hvd/init`` span, the part of ``init_s`` that is not the launcher, the
interpreter and the imports.  Its children (rendezvous, distributed,
backend, runtime) and ``hvd/import`` go on an information line, and on
another the longest stretches of set-up that no span covers."""

from benchmarks.layer_metrics import _program

LAYER = "Launch and start-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    init = _program.init_span(run)
    if init is None:
        return None
    parts = {s["name"]: round(s["end"] - s["start"], 3)
             for s in _program.cold_spans(run)
             if s["name"] == "hvd/import"
             or (s["parent"] == init["name"] and s["start"] >= init["start"])}
    print("bench: hvd/init %.3f s of init_s %.3f: %s"
          % (init["end"] - init["start"], run["init_s"], parts), flush=True)
    print("bench: longest stretches in no span between hvd.init() and the "
          "window, by the span before: %s" % _program.uncovered(run),
          flush=True)
    return init["end"] - init["start"]
