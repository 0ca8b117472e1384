"""Expert parallelism: Switch-style top-1 MoE dispatch over a mesh axis.

The reference's ``alltoall`` collective exists for exactly this workload
(SURVEY §2.3 EP row: "alltoall again the relevant primitive"); here the
full dispatch-compute-combine runs in-graph: capacity-bucketed one-hot
dispatch → ``lax.all_to_all`` to the expert owners → expert FFN →
``all_to_all`` back → gate-weighted combine.  One expert per ``ep``-axis
device; tokens over capacity are dropped (standard Switch semantics).
"""

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def top1_dispatch(gate_logits: jax.Array, capacity: int):
    """Build the Switch dispatch/combine tensors for top-1 routing.

    ``gate_logits``: [T, E].  Returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] gate-weighted, aux_loss scalar).
    """
    T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # [T]
    gate = jnp.max(probs, axis=-1)                         # [T]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [T, E]
    # Position of each token within its expert's bucket.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # [T, E]
    keep = (pos < capacity) & (onehot > 0)
    pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1).astype(
        jnp.int32), capacity, dtype=jnp.float32)           # [T, E, C]
    dispatch = pos_oh * keep[..., None].astype(jnp.float32)
    combine = dispatch * gate[:, None, None]
    # Load-balancing auxiliary loss (Switch eq. 4):
    # aux = E * sum_i f_i * P_i, where f_i is the fraction of tokens
    # routed to expert i and P_i the mean router probability for it.
    # Uniform routing gives aux == 1.0 regardless of E, so literature
    # alpha values (e.g. 0.01) transfer unchanged across expert counts.
    density = onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = (density * density_proxy).sum() * E
    return dispatch, combine, aux


def moe_ffn(x: jax.Array, gate_w: jax.Array, expert_fn: Callable,
            expert_params, axis_name: str = "ep",
            capacity_factor: float = 2.0):
    """Expert-parallel MoE layer body (call inside shard_map).

    Per device: ``x`` [T, D] local tokens, ``expert_params`` the ONE
    local expert's parameters, ``gate_w`` [D, E] replicated gating
    weights with E == axis size.  Returns ([T, D], aux_loss).
    """
    n = lax.psum(1, axis_name)
    T, D = x.shape
    capacity = max(1, int(capacity_factor * T / n))

    logits = x @ gate_w                                    # [T, E]
    dispatch, combine, aux = top1_dispatch(logits, capacity)

    # [T,E,C] x [T,D] -> [E, C, D]: tokens bucketed per target expert.
    buckets = jnp.einsum("tec,td->ecd", dispatch,
                         x.astype(jnp.float32))
    # Exchange: device e receives its expert's bucket from every peer
    # -> [n, C, D] (peer-major).
    received = lax.all_to_all(buckets, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    out = expert_fn(expert_params,
                    received.reshape(n * capacity, D))
    out = out.reshape(n, capacity, D)
    # Route results back to the token owners.
    returned = lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine,
                   returned.astype(jnp.float32))
    return y.astype(x.dtype), lax.pmean(aux, axis_name)
