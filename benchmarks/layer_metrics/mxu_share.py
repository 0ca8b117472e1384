"""Share of the device's operation time (self time, nested operations
counted once) in matrix-multiplication operations, by the trace's own
``hlo_category``, in per cent."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("mxu_share") is None:
        return None
    return 100.0 * trace["mxu_share"]
