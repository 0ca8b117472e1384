"""Median host milliseconds per step from the call of ``tx.update`` to
the entry of the wrapped inner optimizer's ``update``: that interval is
``allreduce_gradients`` and nothing else.  Only a loop that goes
through ``DistributedOptimizer`` has it."""

import statistics

LAYER = "Eager plane"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "samples_per_s_chip"


def read(run: dict):
    exchange = run.get("exchange_s")
    return 1e3 * statistics.median(exchange) if exchange else None
