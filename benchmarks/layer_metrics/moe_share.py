"""Share of the device's operation time in the routed experts' layer,
in per cent: the breakdown's module paths under a ``moe`` module (its
scopes ``router``, ``dispatch``, ``experts`` and ``combine``) and the
grouped matrix products, summed and divided by all self time.  The TPU
compiler lowers ``ragged_dot`` to a kernel of its own that names itself
``ragged-dot-...`` and drops the module path, so the products are
counted by that name; the program has no grouped product outside its
routed experts.

A lower bound: the reduction hands readers the ten groups with most
self time and no others, so what the layer spends in smaller ones is
not counted, and where none of the ten is the layer's the bound is 0.
None where the run has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"

MODULE = "moe"
GROUPED_PRODUCT = "ragged-dot"


def is_routed_experts(group: str) -> bool:
    """``group`` is a key of the breakdown: ``<module path> [category]``,
    or ``<program>/<operation>`` where the trace has no path."""
    parts = group.split(" [")[0].split("/")
    return MODULE in parts or any(p.startswith(GROUPED_PRODUCT)
                                  for p in parts)


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    moe_s = sum(seconds for group, seconds in trace.get("device_ops") or []
                if is_routed_experts(group))
    return 100.0 * moe_s / trace["self_s"] if moe_s else 0.0
