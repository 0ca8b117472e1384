"""Seconds rank 0 spent laying out its steps' state between
``hvd.init()`` returning and the window: the ``hvd/step/shardings``
spans, each the abstract trace of a whole model (``jax.eval_shape``)
and the walk of the partition rules over it.  The trace inside is also
a ``hvd/compile/trace`` span and so part of ``trace_lower_s``: how much
goes on the information line."""

from benchmarks.layer_metrics import _setup

LAYER = "Sharded step"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    at = _setup.instants(run)
    if at is None:
        return None
    spans = _setup.named(run, _setup.SHARDINGS, at[1], at[2])
    if not spans:
        return None
    print("bench: shardings [program, seconds, of which compile spans]: %s"
          % [[s["args"].get("program"), round(s["end"] - s["start"], 3),
              round(_setup.compile_s_inside(run, s), 3)] for s in spans],
          flush=True)
    return sum(s["end"] - s["start"] for s in spans)
