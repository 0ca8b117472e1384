"""Pipeline parallelism over a mesh axis (GPipe-style).

The reference has no pipeline parallelism (SURVEY §2.3 PP row: absent);
on TPU it is a first-class axis.  Implementation: each device on the
``pp`` axis holds ONE stage's parameters; microbatches stream through a
``lax.scan`` whose body applies the local stage and ``ppermute``s
activations one hop forward per tick — the 1F schedule of GPipe with
S + M - 1 ticks for S stages and M microbatches.  Differentiable end to
end (ppermute transposes to the reverse permutation, giving the 1B
backward schedule automatically).

Constraints (standard for SPMD pipelining): every stage maps activations
of one shape to the same shape; stage parameters are a pytree whose
leaves carry a leading stage dimension sharded over ``pp``.
"""

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ._compat import pvary as _pvary


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis_name: str = "pp", vary_axes=()):
    """Run microbatches through the pipeline; returns outputs
    ``[M, ...]`` replicated to every stage.

    ``stage_fn(params, x) -> y`` is this device's stage (its slice of
    ``stage_params``); ``x_microbatches`` is ``[M, B_micro, ...]``
    (replicated input; only stage 0 reads it).  ``vary_axes``: any
    OTHER mesh axes the stage output varies over (e.g. an ``ep`` axis
    used inside the stage) — the scan accumulators must carry the same
    varying-axis type as the stage outputs.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    total = M + n - 1
    fwd_perm = [(i, i + 1) for i in range(n - 1)]

    all_axes = (axis_name,) + tuple(vary_axes)
    buf = _pvary(jnp.zeros_like(x_microbatches[0]), all_axes)
    outputs = _pvary(jnp.zeros_like(x_microbatches), all_axes)

    def tick(carry, t):
        buf, outputs = carry
        # Stage 0 ingests microbatch t while it exists; later stages
        # consume what arrived from the previous stage.
        feed = x_microbatches[jnp.minimum(t, M - 1)]
        x_in = jnp.where(idx == 0, feed, buf)
        y = stage_fn(stage_params, x_in)
        # The last stage emits microbatch t-(n-1) at tick t.
        out_t = t - (n - 1)
        is_emit = jnp.logical_and(idx == n - 1, out_t >= 0)
        updated = lax.dynamic_update_index_in_dim(
            outputs, y, jnp.maximum(out_t, 0), axis=0)
        outputs = jnp.where(is_emit, updated, outputs)
        buf_next = lax.ppermute(y, axis_name, fwd_perm)
        return (buf_next, outputs), None

    (buf, outputs), _ = lax.scan(tick, (buf, outputs),
                                 jnp.arange(total))
    # Outputs live on the last stage; replicate so every stage (and the
    # caller's loss) sees them.  Masked psum = broadcast-from-last.
    outputs = jnp.where(idx == n - 1, outputs,
                        jnp.zeros_like(outputs))
    return lax.psum(outputs, axis_name)


def stack_stage_params(init_fn, rngs, n_stages: int):
    """Host helper: initialize ``n_stages`` stages and stack their
    pytrees along a leading dim (shard it over the pp axis)."""
    trees = [init_fn(rngs[i]) for i in range(n_stages)]
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *trees)
