"""Median step time of the same loop with the plain inner optimizer (no
exchange; a few steps per rank during set-up) over the median step time
in the window, in per cent.  A faster local step lowers it: it is a
per-layer metric on purpose."""

import statistics

LAYER = "Eager plane"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "samples_per_s_chip"


def read(run: dict):
    local, step = run.get("local_step_s"), run.get("step_s")
    if not local or not step:
        return None
    return 100.0 * statistics.median(local) / statistics.median(step)
