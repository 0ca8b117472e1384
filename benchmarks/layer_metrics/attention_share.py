"""Share of the device's operation time in operations whose metadata
names a module path under ``attention/`` other than its four
projections (scores, mask, softmax, dropout, weighted sum), in per
cent.  None where the trace carries no module path."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("attention_share") is None:
        return None
    return 100.0 * trace["attention_share"]
