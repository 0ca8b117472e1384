"""Share of the device's operation time in what latent attention adds
around its kernels, in per cent: the breakdown's module paths under an
``attention`` module's ``kv_down``, ``kv_norm`` and ``kv_up`` (the
narrow down-projection, the latent's norm, the up-projection to every
head's key and value) and its scope ``rotary`` (the rotation of the
rotary parts and the keys' layout, the one rotated key once a head),
summed and divided by all self time.

A lower bound: the reduction hands readers the ten groups with most
self time and no others, so what these spend in smaller ones is not
counted, and where none of the ten lies there the bound is 0.  None
where the run has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "mfu"

MODULE = "attention"
PARTS = ("kv_down", "kv_norm", "kv_up", "rotary")


def is_latent_part(group: str) -> bool:
    """``group`` is a key of the breakdown: ``<module path> [category]``,
    or ``<program>/<operation>`` where the trace has no path."""
    parts = group.split(" [")[0].split("/")
    return any(a == MODULE and b in PARTS for a, b in zip(parts, parts[1:]))


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    latent_s = sum(seconds for group, seconds in trace.get("device_ops") or []
                   if is_latent_part(group))
    return 100.0 * latent_s / trace["self_s"] if latent_s else 0.0
