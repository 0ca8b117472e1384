"""Varying-axis typing helper shared by the parallel modules."""

from jax import lax


def pvary(x, axis_names):
    """Mark x as device-varying over the given axes, skipping axes it
    already varies over (``lax.pcast`` rejects those)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    missing = tuple(a for a in axis_names if a not in x.aval.vma)
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")
