"""Share of the device's operation time in the state-space mixers'
own work, in per cent: the breakdown's module paths under ``mamba/``
other than its two projections (the convolution, the chunked
recurrence, the gated norm), summed and divided by all self time.

A lower bound: the reduction hands readers the ten module paths with
most self time and no others, so what the mixers spend in smaller
paths is not counted, and where none of the ten lies under a ``mamba``
module the bound is 0.  None where the run has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"

MIXER = "mamba"
PROJECTIONS = ("in_proj", "out_proj")


def is_mixer_core(group: str) -> bool:
    """``group`` is a key of the breakdown, ``<module path> [category]``:
    under a ``mamba`` module and in neither of its projections."""
    parts = group.split(" [")[0].split("/")
    if MIXER not in parts:
        return False
    after = parts[parts.index(MIXER) + 1:]
    return not (after and after[0] in PROJECTIONS)


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    core_s = sum(seconds for group, seconds in trace.get("device_ops") or []
                 if is_mixer_core(group))
    return 100.0 * core_s / trace["self_s"] if core_s else 0.0
