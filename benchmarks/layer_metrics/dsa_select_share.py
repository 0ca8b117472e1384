"""Share of the device's operation time in what a learned selection of
keys adds to attention, in per cent: the breakdown's module paths under
an ``attention`` module's scopes ``indexer`` (the indexer's three
projections, its norm and its rotation), ``select`` (the index scores,
the thresholds and the packed mask) and ``indexer_loss`` (the alignment
loss and the indexer's gradients), summed and divided by all self time.
What the selection costs the flash kernels themselves (bits unpacked, a
triangle walked for a quarter of it) is ``flash_selected_roofline``'s
to say.

A lower bound, as ``latent_kv_share`` is and for its reason: the
reduction hands readers the ten groups with most self time and no
others, so what these spend in smaller ones is not counted, and where
none of the ten lies there the bound is 0.  None where the run has no
reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"

MODULE = "attention"
PARTS = ("indexer", "select", "indexer_loss")


def is_selection_part(group: str) -> bool:
    """``group`` is a key of the breakdown: ``<module path> [category]``,
    or ``<program>/<operation>`` where the trace has no path."""
    parts = group.split(" [")[0].split("/")
    return any(a == MODULE and b in PARTS for a, b in zip(parts, parts[1:]))


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    added_s = sum(seconds for group, seconds in trace.get("device_ops") or []
                  if is_selection_part(group))
    return 100.0 * added_s / trace["self_s"] if added_s else 0.0
