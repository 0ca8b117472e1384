"""Device milliseconds per step, averaged over the chips, in collective
operations on the device's operation line.  An asynchronous all-reduce
runs beside the compute; what shows on that line is its start and the
``-done`` that waits for it, so this is the part no compute hid.  None
where the traced programs hold no collective."""

LAYER = "Sharded step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_s_chip"


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("collective_s") or \
            not run.get("traced_steps"):
        return None
    return 1e3 * trace["collective_s"] / run["traced_steps"]
