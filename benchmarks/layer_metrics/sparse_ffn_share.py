"""Share of the device's operation time in a sparse feed-forward that
has a shared expert beside its routed ones, in per cent: the
breakdown's module paths under a ``moe`` module, which here holds the
module ``shared`` (the always-on expert) beside the routed experts'
scopes ``router``, ``dispatch``, ``experts`` and ``combine``, and the
grouped matrix products by the compiler's kernel name, summed and
divided by all self time: ``moe_share``'s reading of a layer that has
one part more.

A lower bound, as that one is: the reduction hands readers the ten
groups with most self time and no others, so what the layer spends in
smaller ones is not counted, and where none of the ten is the layer's
the bound is 0.  None where the run has no reduced trace."""

from benchmarks.layer_metrics.moe_share import is_routed_experts

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    ffn_s = sum(seconds for group, seconds in trace.get("device_ops") or []
                if is_routed_experts(group))
    return 100.0 * ffn_s / trace["self_s"] if ffn_s else 0.0
