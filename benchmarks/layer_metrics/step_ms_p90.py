"""90th percentile of per-step host milliseconds in the traced run's
window, each step fetched; the sample count goes on an earlier line."""

LAYER = "Device"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "samples_per_s_chip"


def read(run: dict):
    steps = sorted(run.get("step_s") or [])
    if len(steps) < 20:
        return None
    return 1e3 * steps[min(len(steps) - 1, int(0.9 * len(steps)))]
