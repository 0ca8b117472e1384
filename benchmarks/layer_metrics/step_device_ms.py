"""Device-busy milliseconds per step in the traced window: the union of
the device's operation intervals over the traced steps."""

LAYER = "Sharded step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_s_chip"


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("traced_steps"):
        return None
    return 1e3 * trace["busy_s"] / run["traced_steps"]
