"""Median milliseconds a step inside ``hvd/negotiate`` spans (the controller's
``compute_response_list`` on the background thread), from one ``hvd/exchange`` to the next, over
the traced steps.  Waits, as ``exchange_ms`` does, for the eager cell,
and for a reduction of the trace that keeps the ``hvd/`` spans."""

from benchmarks.layer_metrics import _program

LAYER = "Eager plane"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "samples_per_s_chip"


def read(run: dict):
    spans = _program.traced_spans(run)
    return None if spans is None else \
        _program.per_step_ms(spans, "hvd/negotiate")
