"""Expert layers.  Two live here and they share nothing yet
(ROADMAP.md, Queue 3, D18):

* :func:`routed_experts`: top-k routed experts of which this device
  holds a run, dropless: scores over ALL experts (a sigmoid and a
  selection bias, :func:`sigmoid_top_k`, or a softmax,
  :func:`softmax_top_k`: the caller says which), the (token, expert)
  pairs whose expert is held sorted by expert, gathered, run
  through three grouped matrix products and added back into their
  tokens.  The products are ``jax.lax.ragged_dot``, which the TPU
  compiler lowers to a Mosaic kernel over row tiles of 512, or, where
  the wide passes below are kernels, a custom VJP over the three
  grouped kernels of ``ops/pallas_moe.py`` (:func:`_grouped`), which
  walk the (row tile of 128, group) pairs that hold a row: the same
  arithmetic, and ``ragged_dot`` the tests' oracle.  No capacity and no exchange: on one
  chip of an expert-parallel group it computes this chip's part of
  the layer.  The sorted buffer is as long as the routing can fill it;
  the pairs held are a prefix of it, its length known on the device
  when the plan is made, and that is what says which rows hold a pair
  and how far the rows' gates are gathered (:func:`_gates_of_rows`).  The wide
  passes (the rows' gathers, tokens to rows and rows to tokens, the sum
  of the rows' two cotangents, the gated product and its backward) stop
  there too where they are Pallas kernels (``ops/pallas_moe.py``: the
  pairs' count a prefetched scalar, no row past it fetched, computed or
  written): on one TPU device, where a caller says so
  (:func:`on_one_tpu`) and the kernels' tiles divide the shapes
  (:func:`kernels_fit`).  Elsewhere (the CPU, a mesh of several devices,
  since GSPMD does not partition a Mosaic call, ``init``) they are
  XLA's gathers and fusions, which walk the whole buffer whatever it
  holds: cut into chunks inside an XLA loop they cost on the v5e what
  they saved (``PERF.md``, PR 33).
* :func:`moe_ffn` with :func:`top1_dispatch`: Switch-style top-1
  dispatch over a mesh axis.  The reference's ``alltoall`` collective
  exists for exactly this workload (SURVEY §2.3 EP row: "alltoall again
  the relevant primitive"); here the full dispatch-compute-combine runs
  in-graph: capacity-bucketed one-hot dispatch → ``lax.all_to_all`` to
  the expert owners → expert FFN → ``all_to_all`` back → gate-weighted
  combine.  One expert per ``ep``-axis device; tokens over capacity are
  dropped (standard Switch semantics).
"""

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

# What a recomputed layer may keep of :func:`routed_experts`, by
# ``checkpoint_name``: the sorted rows (or they are gathered again) and
# the two first products' outputs (or those products run twice).
ROWS_NAME, EXPERT_GATE_UP_NAME = "moe_rows", "moe_gate_up"
# And what it MUST keep: the choice.  A recomputed forward pass is
# another program than the first (other fusions, other roundings of the
# router's input), and a near-tie it decides the other way sends the
# backward pass's cotangents through experts the forward pass never
# ran: one such token of 128 moved a tiny layer's gradients by 130 %.
CHOICE_NAME = "moe_chosen"
# Added to the sum of the chosen gates before they are divided by it,
# where the caller names no other (a family's own: ``gate_sum_eps``).
GATE_SUM_EPS = 1e-6
# Rows of the sorted buffer a walk (:func:`_gates_of_rows`) takes at a
# time: it stops at the first chunk that starts past the pairs.  A
# multiple of the grouped kernels' row tiles (128; ``ragged_dot``'s 512).
WALK_CHUNK_ROWS = 2048


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


class Routing(NamedTuple):
    """What the router decided for ``T`` tokens: the ``top_k`` experts
    each chose, of ALL experts, and the gate of each."""
    chosen: jax.Array   # [T, top_k] int32
    gates: jax.Array    # [T, top_k] float32


def sigmoid_top_k(x: jax.Array, router_kernel: jax.Array,
                  selection_bias: jax.Array, top_k: int,
                  normalize: bool = True, scale: float = 1.0,
                  gate_sum_eps: float = GATE_SUM_EPS,
                  chosen: Optional[jax.Array] = None) -> Routing:
    """Scores ``s = sigmoid(x @ W_r)`` over all experts; the ``top_k``
    of ``s + selection_bias`` are chosen (the bias selects and no
    gradient reaches it); a chosen expert's gate is its ``s``, divided
    by the chosen gates' sum plus ``gate_sum_eps`` where ``normalize``,
    times ``scale``.  All of it in float32 whatever ``x``'s type, the
    product at full precision: it is as many columns wide as there are
    experts, and a choice is a comparison.  ``chosen`` ``[T, top_k]``
    hands the choice over (a comparison that holds two computations to
    ONE discrete choice: two compiles of one forward pass decide a few
    near-ties differently); scores and gates are still this router's."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    if chosen is None:
        _, chosen = lax.top_k(scores + lax.stop_gradient(selection_bias),
                              top_k)
    chosen = checkpoint_name(chosen, CHOICE_NAME)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        gates = gates / (gates.sum(-1, keepdims=True) + gate_sum_eps)
    return Routing(chosen.astype(jnp.int32), gates * scale)


def softmax_top_k(x: jax.Array, router_kernel: jax.Array, top_k: int,
                  normalize: bool = True,
                  chosen: Optional[jax.Array] = None) -> Routing:
    """Scores ``p = softmax(x @ W_r)`` over all experts; the ``top_k``
    largest are chosen; a chosen expert's gate is its ``p``, divided by
    the chosen gates' sum where ``normalize``.  No selection bias and
    no scale.  Float32 and at full precision as :func:`sigmoid_top_k`
    is, and for its reasons; ``chosen`` hands the choice over as
    there."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    if chosen is None:
        _, chosen = lax.top_k(scores, top_k)
    chosen = checkpoint_name(chosen, CHOICE_NAME)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        gates = gates / gates.sum(-1, keepdims=True)
    return Routing(chosen.astype(jnp.int32), gates)


def moved_bias(bias: jax.Array, counts: jax.Array, step: float) -> jax.Array:
    """The selection bias of :func:`sigmoid_top_k` after one step of
    the rule that balances the experts' loads without a loss (the
    auxiliary-loss-free balancing of DeepSeek-V3, as torchtitan applies
    it under ``load_balance_coeff``): with ``counts`` ``[experts]`` the
    (token, choice) pairs that chose each of ALL the router's experts
    over the step's whole batch, ``d_e = step * sign(mean(counts) -
    counts_e)`` (an expert under the mean load is raised, one over it
    lowered, one at it left alone) and ``bias_e + d_e - mean(d)``: the
    bias stays centred.  Written on the signs, ``step * (sign -
    mean(sign))``: their sum is a whole number whatever order it is
    added in, so two programs that count one choice move the bias alike
    but for the last bit (a compiler may round the product and the sum
    once or twice).  No gradient, no optimizer: the caller applies it
    to the ``expert_bias`` leaves after the optimizer's update."""
    counts = counts.astype(jnp.float32)
    sign = jnp.sign(counts.mean() - counts)
    return bias + step * (sign - sign.mean())


def dispatch_rows(tokens: int, top_k: int, held: int) -> int:
    """Length of one layer's sorted buffer: as many pairs as the routing
    can give the experts held, every token choosing them alone.  Not a
    capacity: no routing overflows it; and not what a walk touches
    (:func:`rows_walked`)."""
    return tokens * min(top_k, held)


def rows_walked(pairs: int, rows: int) -> int:
    """Rows of a buffer of ``rows`` that a walk touches where
    ``pairs`` of them hold a pair: the chunks of ``WALK_CHUNK_ROWS``
    that start before the pairs end."""
    return min(rows, -(-pairs // WALK_CHUNK_ROWS) * WALK_CHUNK_ROWS)


def blocks_walked(pairs: int, rows: int, block: Optional[int] = None) -> int:
    """Grid steps of a kernel over a buffer of ``rows`` that do work
    where ``pairs`` of them hold a pair: the blocks of ``block`` rows
    (the kernels' ``ROW_BLOCK``, ``hvd_moe_kernel_block_rows``) that
    start before the pairs end.  Over the grid's length,
    ``-(-rows // block)``, the share of blocks that did work."""
    block = min(block or _kernels().ROW_BLOCK, rows)
    return min(-(-rows // block), -(-pairs // block))


def dispatch_bytes(tokens: int, hidden: int, width: int, top_k: int,
                   held: int, itemsize: int) -> int:
    """Bytes one layer's forward materialises on one device between the
    router and the combine: the choice, the gates and each pair's place
    in the sort [T, k], each row's token and gate [R], the groups'
    sizes; the sorted rows [R, hidden]; gate, up and their gated
    product [R, width]; the experts' output [R, hidden]."""
    rows = dispatch_rows(tokens, top_k, held)
    indices = tokens * top_k * (4 + 4 + 4) + rows * (4 + 4) + held * 4
    return indices + itemsize * rows * (2 * hidden + 3 * width)


class Plan(NamedTuple):
    """The dispatch's plan for ``T`` tokens and ``R`` rows
    (:func:`dispatch_rows`), integers all.  All ``T x top_k`` pairs are
    sorted by expert, stably (by token within an expert), those whose
    expert is not held after every pair that is; the first ``R`` places
    are the row buffer."""
    token: jax.Array        # [R] int32: the token of a row; 0 past the pairs
    valid: jax.Array        # [R] bool: the row holds a pair
    group_sizes: jax.Array  # [held] int32: rows of each expert held
    place: jax.Array        # [T, top_k] int32: where the sort put a pair
    is_held: jax.Array      # [T, top_k] bool: its expert is held here


def held_pairs(routing: Routing, first_expert: int, held: int):
    """Of the ``T x top_k`` pairs, those whose expert lies in
    ``[first_expert, first_expert + held)``, sorted by expert:
    ``(Plan, row_gate)``, ``row_gate`` ``[R]`` the gate of each row's
    pair and 0 past the pairs (a gather of ``routing.gates`` as far as
    the pairs go, through which their gradient comes)."""
    tokens, top_k = routing.chosen.shape
    rows = dispatch_rows(tokens, top_k, held)
    local = routing.chosen.reshape(-1) - first_expert
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=jnp.int32), unique_indices=True)
    order = order[:rows]
    group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    # The pairs held are sorted first: they are the rows before their
    # count, and no gather has to ask.
    valid = jnp.arange(rows) < group_sizes.sum()
    plan = Plan(
        token=jnp.where(valid, order // top_k, 0), valid=valid,
        group_sizes=group_sizes, place=place.reshape(tokens, top_k),
        is_held=is_held.reshape(tokens, top_k))
    return plan, _gates_of_rows(routing.gates, order, plan)


def _rows_of_pairs(buffer, plan: Plan):
    """``[top_k, T, ...]``: each pair's row of ``buffer`` ``[R, ...]``,
    zeros for a pair that has none.  A gather over places no two pairs
    share; a pair not held lies past the pairs held, where the buffer
    holds whatever the products left, or past the buffer.  The pairs of
    one token lie ``T`` rows apart, so that a sum over them runs over
    the major axis: as ``[T, top_k, D]`` the compiler lays ``top_k``
    into the tiles' eight sublanes and copies the gather's output to
    get it there (0.45 ms a gather of 32768 rows on the v5e)."""
    got = buffer.at[plan.place.T].get(mode="fill", fill_value=0,
                                      unique_indices=True)
    is_held = plan.is_held.T
    return jnp.where(is_held.reshape(is_held.shape + (1,) * (got.ndim - 2)),
                     got, 0)


def _pairs_of_tokens(per_token, plan: Plan):
    """``[R, D]``: for each row its token's row of ``per_token`` ``[T,
    D]``, zeros past the pairs."""
    return jnp.where(plan.valid[:, None],
                     jnp.take(per_token, plan.token, axis=0), 0)


@jax.custom_vjp
@jax.jit
def _gates_of_rows(gates, order, plan: Plan):
    """``[R]``: the gate of each row's pair, ``gates`` ``[T, top_k]`` at
    ``order`` (the pair a row holds), 0 past the pairs; its transpose a
    gather as well, of each pair's row.

    A walk: the rows are taken ``WALK_CHUNK_ROWS`` at a time in a loop
    that stops at the first chunk that starts past the pairs, and
    leaves zeros from there on.  The pairs' count is read on the
    device, so the loop makes as many trips as the routing filled
    chunks, and the traced program holds its body once.  (A gather of
    single numbers costs the v5e as much a row as a gather of
    2048-wide rows: 0.23 ms for 32768.)  JAX differentiates no loop of
    such a length, hence the custom VJP.  Where the buffer is no whole
    number of chunks the last one starts early and writes some rows a
    second time, the same values.  What a walk costs beside its chunks
    is the buffer's zeros and a copy of each chunk into it: nothing for
    this vector, as much as it saves for the ``[R, D]`` buffers, which
    is why they are not walked (``PERF.md``, PR 33).  Under
    ``jax.jit``, so that the layers of a step, and the programs of a
    process, trace the loop once between them."""
    flat, rows = gates.reshape(-1), order.size
    size = min(WALK_CHUNK_ROWS, rows)

    def trip(c, row_gate):
        start = jnp.minimum(c * size, rows - size)
        pair, valid = (lax.dynamic_slice_in_dim(of_row, start, size)
                       for of_row in (order, plan.valid))
        return lax.dynamic_update_slice_in_dim(
            row_gate, jnp.where(valid, jnp.take(flat, pair), 0), start, 0)
    return lax.fori_loop(0, (plan.group_sizes.sum() + size - 1) // size,
                         trip, jnp.zeros(rows, flat.dtype))


def _gates_of_rows_fwd(gates, order, plan):
    return _gates_of_rows(gates, order, plan), plan


def _gates_of_rows_bwd(plan, d_row_gate):
    return _rows_of_pairs(d_row_gate, plan).T, None, None


_gates_of_rows.defvjp(_gates_of_rows_fwd, _gates_of_rows_bwd)


def _kernels():
    """``ops/pallas_moe.py``, imported where a layer is traced as
    kernels, as the models import the flash kernels: Pallas takes a
    second to import, and a process that runs no kernel does not pay
    it."""
    from ..ops import pallas_moe
    return pallas_moe


def _kernel(name: str, *args):
    """One of ``ops/pallas_moe.py``'s jitted functions, called in the
    one tracing context they share (a jaxpr is kept by it too)."""
    pallas_moe = _kernels()
    with pallas_moe.one_trace_context():
        return getattr(pallas_moe, name)(*args)


def on_one_tpu(mesh) -> bool:
    """Whether arrays laid out on ``mesh`` (None: a model applied
    directly, on the default backend) lie on ONE TPU device: where the
    kernels can run.  Read where the models read the platform for their
    attention (``models/gpt.py`` ``attention_impl``); GSPMD does not
    partition a Mosaic call, so a mesh of several devices keeps XLA's
    passes."""
    if mesh is None:
        return jax.default_backend() == "tpu"
    return mesh.devices.flat[0].platform == "tpu" and mesh.size == 1


def kernels_fit(tokens: int, top_k: int, held: int, hidden: int, width: int,
                dtype) -> bool:
    """Whether the kernels' tiles divide a layer's static shapes: a row
    of ``hidden`` whole (8, 128) tiles of 32-bit words (2048 bfloat16,
    1024 float32), the experts' ``width`` whole lanes, buffer and
    tokens whole blocks, the buffer whole row tiles of the grouped
    kernels.  The one rule for every kernel of the layer: the four
    sparse cells' shapes fit; the tests' tiny models' do not, and take
    XLA's passes and ``ragged_dot`` anywhere."""
    pallas_moe = _kernels()
    rows = dispatch_rows(tokens, top_k, held)
    return (pallas_moe.row_sublanes(hidden, dtype) > 0
            and width % pallas_moe.LANES == 0
            and rows % min(rows, pallas_moe.ROW_BLOCK) == 0
            and rows % pallas_moe.grouped_tile(rows) == 0
            and tokens % min(tokens, pallas_moe.TOKEN_BLOCK) == 0
            and min(rows, tokens) % pallas_moe.SUBLANES == 0)


def _pairs_held_first(plan: Plan):
    """``([T, top_k] int32, [T] int32)``: the rows of each token's pairs
    held, moved to the front of its ``top_k`` in their order, and how
    many they are: a pass over them then asks no pair whether it is
    held.  Elementwise over ``[T, top_k, top_k]``: no sort and no
    gather of single numbers, which cost the v5e what a gather of rows
    does."""
    top_k = plan.place.shape[1]
    rank = jnp.cumsum(plan.is_held, axis=1) - 1
    to_slot = plan.is_held[:, :, None] & (
        rank[:, :, None] == jnp.arange(top_k))
    return (jnp.where(to_slot, plan.place[:, :, None], 0).sum(1),
            plan.is_held.sum(1).astype(jnp.int32))


# Dispatch and combine are each a gather, forward and backward, and
# each other's transpose: left to autodiff the transposes are
# scatter-adds in which up to ``top_k`` rows, and every row past the
# pairs, meet in one token.  ``kernels`` says how a gather is made
# (static: a custom VJP's rule calls the kernels' jitted functions, it
# is not inside them); as a kernel it leaves the rows past the pairs as
# the memory held them.  Who guarantees what about those rows: the
# grouped products read and write rows by group, so neither a buffer's
# tail nor a cotangent's reaches them; these rules read a buffer by the
# pairs' places and count alone; and the gated product's rule does as
# much.  Nothing else is handed a buffer.

def _rows_of_tokens(x, plan: Plan, kernels: bool):
    """``rows[r] = x[token[r]]``."""
    if kernels:
        return _kernel("rows_of_tokens", x, plan.token,
                       plan.group_sizes.sum())
    return _pairs_of_tokens(x, plan)


def _tokens_of_rows(outs, plan: Plan, kernels: bool):
    """``y[t] = sum of out[place] over t's pairs held``, in float32,
    ``out`` the sum of ``outs``."""
    if kernels:
        pairs = plan.group_sizes.sum()
        # Two cotangents are added a row that holds a pair at a time,
        # the sum written over the first.
        out = outs[0] if len(outs) == 1 else _kernel("add_rows", *outs, pairs)
        return _kernel("tokens_of_rows", out, *_pairs_held_first(plan),
                       pairs)
    out = sum(outs[1:], outs[0])
    return _rows_of_pairs(out, plan).astype(jnp.float32).sum(0).astype(
        out.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, plan: Plan, kernels: bool):
    """The rows of tokens, handed out twice, once for each of the two
    products that read them: their cotangents then come back apart and
    are added where the rows are read, which as a kernel is a row that
    holds a pair at a time (autodiff's ``add_any`` is a pass over the
    whole buffer: 1.5 ms a layer at 81920 rows)."""
    rows = _rows_of_tokens(x, plan, kernels)
    return rows, rows


def _dispatch_fwd(x, plan, kernels):
    return _dispatch(x, plan, kernels), plan


def _dispatch_bwd(kernels, plan, d_rows):
    return _tokens_of_rows(d_rows, plan, kernels), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine(out, plan: Plan, kernels: bool):
    """The tokens of rows."""
    return _tokens_of_rows((out,), plan, kernels)


def _combine_fwd(out, plan, kernels):
    return _combine(out, plan, kernels), plan


def _combine_bwd(kernels, plan, d_y):
    return _rows_of_tokens(d_y, plan, kernels), None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _gated(a, b, row_gate, pairs):
    """``silu(a) * b * row_gate`` over the rows that hold a pair, as
    kernels forward and backward: float32 throughout and rounded once;
    past the pairs, values and cotangents are what the memory held."""
    return _kernel("gated", a, b, row_gate, pairs)


def _gated_fwd(a, b, row_gate, pairs):
    return _gated(a, b, row_gate, pairs), (a, b, row_gate, pairs)


def _gated_bwd(kept, d_gated):
    a, b, row_gate, pairs = kept
    return (*_kernel("gated_bwd", a, b, row_gate, d_gated, pairs), None)


_gated.defvjp(_gated_fwd, _gated_bwd)


@jax.custom_vjp
def _grouped(lhs, weights, walk):
    """``lhs[r] @ weights[e(r)]`` over the rows that hold a pair, as
    kernels forward and in both transposes (``lax.ragged_dot``'s
    arithmetic: operands of ``lhs``'s type, float32 accumulation, one
    rounding).  Every row tile a group reaches is written whole, zeros
    past the last group's end; a tile past them holds what the memory
    held."""
    return _kernel("grouped_rows", lhs, weights, walk)


def _grouped_fwd(lhs, weights, walk):
    return _grouped(lhs, weights, walk), (lhs, weights, walk)


def _grouped_bwd(kept, d_out):
    lhs, weights, walk = kept
    return (_kernel("grouped_rows_t", d_out, weights, walk),
            _kernel("grouped_weights", lhs, d_out, walk), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def routed_experts(x: jax.Array, router_kernel: jax.Array,
                   selection_bias: jax.Array, gate_kernels: jax.Array,
                   up_kernels: jax.Array, down_kernels: jax.Array, *,
                   first_expert: int, top_k: int, normalize: bool = True,
                   scale: float = 1.0, gate_sum_eps: float = GATE_SUM_EPS,
                   chosen: Optional[jax.Array] = None,
                   router: Optional[Callable] = None, kernels: bool = False):
    """Top-k routed SwiGLU experts, the part of the layer that the
    experts HELD give: ``y_t = sum over the chosen e held of g_te *
    expert_e(x_t)``, ``expert_e(x) = (silu(x W1_e) * (x W3_e)) W2_e``.

    ``router`` says how tokens choose and what the gates are: None is
    :func:`sigmoid_top_k` with ``selection_bias``, ``scale`` and
    ``gate_sum_eps``; another (:func:`softmax_top_k`) is called as
    ``router(x, router_kernel, top_k, normalize, chosen)`` and those
    three are not read (``selection_bias`` may be None).  ``kernels``:
    the caller found the layer's tokens on one TPU device
    (:func:`on_one_tpu`; the models ask outside ``init``, which wants
    the parameters' shapes and nothing of the layer), so the wide
    passes AND the three grouped products with their transposes are
    Pallas kernels wherever their tiles divide the shapes
    (:func:`kernels_fit`); the results are the same to a rounding.

    ``x``: ``[T, D]``.  ``router_kernel`` ``[D, E]`` and
    ``selection_bias`` ``[E]`` are over ALL ``E`` experts; the stacked
    ``gate_kernels`` and ``up_kernels`` ``[held, D, F]`` and
    ``down_kernels`` ``[held, F, D]`` are those of experts
    ``first_expert .. first_expert + held - 1``.  What an expert held
    elsewhere would add is left out, and nothing stands in for it.

    No pair is dropped, by construction: the row buffer is as long as
    the routing can make it (:func:`dispatch_rows`), so its size grows
    with ``T x top_k`` and not with ``T x E``.  What grows with the
    pairs really sent is the grouped products' work, which skip the
    rows past the groups' ends (``ragged_dot`` by tiles of 512 rows, the
    kernels by the (tile of 128, group) pairs that hold a row, walked
    from the plan's ``group_sizes``), the gather of the rows' gates, which
    stops at the first chunk that starts past them
    (:func:`_gates_of_rows`), and, as kernels, the rows' gathers both
    ways, the sum of the rows' two cotangents and the gated product
    with its backward: a block of rows that starts past the pairs is
    neither fetched nor computed nor written, and holds what the memory
    held (every reader masks by the count or the places before a
    nonlinearity or a sum).  As XLA's passes those walk the whole
    buffer and leave zeros there.  The extent is read from the plan, on
    the device, each step, and no setting stands between a router that
    sends every pair here and one that sends none.  Router, choice and
    gates are float32; the products take ``x``'s type and accumulate
    in float32.  A pair's gate multiplies its row BEFORE the last
    product (the same sum), so that the gates' gradient needs the
    gated product ``[R, F]``, which the backward pass has, and not the
    experts' output ``[R, D]``, which it would compute again.
    Returns ``(y [T, D], Routing)``."""
    held = gate_kernels.shape[0]
    dtype = x.dtype
    kernels = kernels and kernels_fit(x.shape[0], top_k, held, x.shape[1],
                                      gate_kernels.shape[2], dtype)
    with jax.named_scope("router"):
        if router is None:
            routing = sigmoid_top_k(x, router_kernel, selection_bias, top_k,
                                    normalize, scale, gate_sum_eps, chosen)
        else:
            routing = router(x, router_kernel, top_k, normalize, chosen)
    with jax.named_scope("dispatch"):
        plan, row_gate = held_pairs(routing, first_expert, held)
        rows, rows_again = (checkpoint_name(rows, ROWS_NAME)
                            for rows in _dispatch(x, plan, kernels))
    with jax.named_scope("experts"):
        # The (row tile, group) pairs the three products and their six
        # transposes walk, made once.
        walk = kernels and _kernel("grouped_walk", plan.group_sizes,
                                   rows.shape[0])

        def grouped(lhs, weights):
            if kernels:
                return _grouped(lhs, weights.astype(dtype), walk)
            return lax.ragged_dot(lhs, weights.astype(dtype),
                                  plan.group_sizes,
                                  preferred_element_type=dtype)
        a = checkpoint_name(grouped(rows, gate_kernels), EXPERT_GATE_UP_NAME)
        b = checkpoint_name(grouped(rows_again, up_kernels),
                            EXPERT_GATE_UP_NAME)
        if kernels:
            gated = _gated(a, b, row_gate, plan.group_sizes.sum())
        else:
            # A row past the groups' ends holds whatever the products
            # left there, which need not be finite: it is masked BEFORE
            # the nonlinearity, so that neither its value nor its
            # derivative times a zero cotangent can be nan.
            keep = plan.valid[:, None]
            gated = (jax.nn.silu(jnp.where(keep, a, 0))
                     * jnp.where(keep, b, 0))
            gated = (gated.astype(jnp.float32)
                     * row_gate[:, None]).astype(dtype)
        out = grouped(gated, down_kernels)
    with jax.named_scope("combine"):
        y = _combine(out, plan, kernels)
    return y, routing
