"""1 - the union of the device's operation intervals over the traced
window, on rank 0's chip, in per cent."""

LAYER = "Device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_s_chip"


def read(run: dict):
    trace = run.get("trace")
    return 100.0 * trace["idle_share"] if trace else None
