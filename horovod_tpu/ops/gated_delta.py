"""The gated delta rule in its chunked form (Yang, Kautz and
Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464, section
3.3; the WY form of Yang et al. 2024, arXiv:2406.06484).

The recurrence, per head, with a scalar log-decay ``g_t <= 0`` and a
write strength ``beta_t`` a position and a state ``S`` of ``[keys,
values]`` from zeros::

    S = exp(g_t) * S
    r = S^T k_t                            # what the state holds at k_t
    S = S + k_t (outer) (beta_t * (v_t - r))
    o_t = S^T q_t

Every position first READS the state at its key and writes the
correction, so a chunk's writes depend on each other.  Within a chunk
of ``L`` positions, with ``cum`` the running sum of ``g``, the
corrections solve a unit lower-triangular system::

    A = strictly_lower((k beta) k^T * exp(cum_i - cum_j))
    T = (I + A)^-1
    w = T (k beta exp(cum)),  u = T (v beta)

and the rest is matrix products, over the chunks in order with the
carried state: ``v_new = u - w S``; ``o = (q exp(cum)) S + lower(q k^T
* exp(cum_i - cum_j)) v_new``; ``S = exp(cum_end) S + (k exp(cum_end -
cum))^T v_new``.

The sibling of ``ops/ssd.py`` and in its manner: plain ``jax.numpy``
einsums, the stages under ``jax.named_scope``s, differentiable by
autodiff but for two stages whose backward is written out.  The walk
over the chunks (``_walk``): one reversed scan over the states the
forward walk left, so a recomputed layer that kept them walks nothing
again.  And, on one TPU device at shapes their tiles divide
(:func:`solved_in_vmem`; the model reads the device off its mesh, no
argument or variable chooses), the chunks' systems, the ``wy`` scope:
the two Pallas kernels of ``ops/pallas_gated_delta.py``, a grid step of
which holds a few (head, chunk) systems in VMEM.  There the keys'
square, ``A``, the right-hand sides, ``T`` and, in the backward kernel,
all of them again with their cotangents are made in VMEM and never
reach HBM: the forward reads ``k``, ``v``, ``beta`` and ``cum`` and
writes ``w`` and ``u``, the backward reads those four and ``dw``,
``du`` and writes four cotangents.  Everything else stays XLA's: the
decays, the walk, the two output stages, and for every other input
(a CPU, several devices, the tiny models' widths) the systems
themselves, :func:`_wy_by_xla`, which is also the kernels' oracle.

``g``, its sums, ``A`` and the carried state are float32.  As XLA's
operations ``T`` is never formed: ``w`` and ``u`` come out of one
triangular solve of ``(I + A) [w | u] = [k beta exp(cum) | v beta]``,
in float32 at full precision (rows in turn inside XLA's solver:
backward stable wherever a chunk's keys are alike, where the product
``(I - A)(I + A^2)(I + A^4)...`` that the matrix unit would take loses
its low bits to the powers' growth, and on the v5e the quicker of the
two: 8.7 ms against 13.8 for the inverses of a layer at 2 x 8192,
PERF.md, PR 39).  The kernels solve by blocked forward substitution,
the form XLA's expander has: the diagonal blocks by substitution over
their rows, the blocks below them by float32 products at full
precision; that product stays out there too.  The wide products take
their operands in ``v``'s dtype and accumulate in float32.  What the
two operators do not share: SSD's state is only decayed and added to,
so its chunks' states are made side by side and its scan carries sums;
here a chunk's writes need the state it is entered with, so the scan
over the chunks carries the products themselves.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.scipy.linalg import solve_triangular

from ..common import metrics
from .ssd import chunks_of

# What a recomputed layer may keep of the operator, by
# ``checkpoint_name``: ``T``'s products ``w`` and ``u`` (or the keys'
# square and the solve are made again), and what the walk over the
# chunks leaves at every chunk, the state it is entered with and
# ``v_new``.  The second names the walk's two STACKED outputs, once,
# outside its loop, and they are all its written-out backward reads of
# it: a layer that kept them recomputes no walk (the step holds one
# forward and one backward ``while`` a layer), and one that dropped
# them makes the plain walk again.  Named inside the loop's body and
# differentiated by autodiff, each recomputed walk was handed the kept
# stack and copied the whole of it every trip on the v5e: 54 ms a
# layer at 1 x 8192 where the walk is 3 (PERF.md, PR 39 and PR 40).
WY_NAME, STATES_NAME = "gdn_wy", "gdn_states"


def scan_bytes(batch: int, seq: int, heads: int, key_dim: int,
               value_dim: int, chunk: int, itemsize: int,
               in_vmem: bool = False) -> int:
    """Bytes of the arrays :func:`gated_delta_chunked` materialises for
    one layer's forward pass with ``batch`` sequences on the device:
    the decay between two positions, ``A`` (float32; not where the
    chunks' systems are made and solved ``in_vmem``, by the kernels:
    :func:`solved_in_vmem`) and the queries' square against the keys
    (float32, and as weights in the compute dtype), each ``[batch,
    heads, count, length, length]``; ``w`` (compute dtype), ``u`` and
    ``v_new`` (float32) of every position; and the state every chunk is
    entered with, ``[batch, count, heads, key_dim, value_dim]``
    float32."""
    count, length = chunks_of(seq, chunk)
    square = batch * heads * count * length * length
    positions = batch * count * length * heads
    states = batch * count * heads * key_dim * value_dim
    return (square * ((2 if in_vmem else 3) * 4 + itemsize)
            + positions * (key_dim * itemsize + 2 * value_dim * 4)
            + states * 4)


_WALK_TRACES = metrics.gauge(
    "hvd_gdn_walk_traces",
    "Traces of the delta rule's walk over the chunks this process made, "
    "by pass: forward (the scan that carries the state) or backward (its "
    "written-out reverse scan, which a step differentiates through in "
    "place of autodiff's); one a distinct shape and trace context")
KERNEL_TRACES = metrics.gauge(
    "hvd_gdn_kernel_traces",
    "Traces of the delta rule's within-chunk Pallas kernels this process "
    "made, by kernel (wy_fwd, wy_bwd; incremented inside each kernel's "
    "jitted function, so it counts traces of the body, not calls: one a "
    "distinct shape and configuration context, however many layers and "
    "programs call it; 0 where the operator was traced and no layer took "
    "the kernels)")


def _rounded(t, dtype):
    """``t`` rounded to the compute dtype and handed over as float32:
    the CPU backend refuses a bfloat16 product inside a loop's body,
    and the TPU rounds a float32 product's operands to bfloat16 itself,
    one pass either way."""
    return t.astype(dtype).astype(jnp.float32)


@jax.custom_vjp
def _walk(w, u, k_to_end, chunk_decay):
    """The walk over the chunks, the chunks first in every operand:
    ``w`` and ``k_to_end`` ``[count, batch, length, heads, key_dim]`` in
    the compute dtype, ``u`` ``[count, batch, length, heads,
    value_dim]`` and ``chunk_decay`` ``[count, batch, heads]`` float32.
    Returns the state every chunk is entered with, ``[count, batch,
    heads, key_dim, value_dim]``, and ``v_new``, as ``u``; float32
    both.  The state the last chunk leaves is not returned."""
    return _walk_forward(w, u, k_to_end, chunk_decay)[0]


def _walk_forward(w, u, k_to_end, chunk_decay):
    _WALK_TRACES.inc(1, **{"pass": "forward"})
    _WALK_TRACES.inc(0, **{"pass": "backward"})   # reads 0, not absent
    dtype = w.dtype

    def step(carried, of_chunk):
        w_c, u_c, k_c, decay = of_chunk
        v_new = u_c - jnp.einsum("blhk,bhkv->blhv", _rounded(w_c, dtype),
                                 _rounded(carried, dtype))
        left = carried * decay[..., None, None] + jnp.einsum(
            "blhk,blhv->bhkv", _rounded(k_c, dtype), _rounded(v_new, dtype))
        return left, (carried, v_new)
    _, batch, _, heads, key_dim = w.shape
    _, (entered, v_new) = lax.scan(
        step, jnp.zeros((batch, heads, key_dim, u.shape[-1]), jnp.float32),
        (w, u, k_to_end, chunk_decay))
    entered = checkpoint_name(entered, STATES_NAME)
    v_new = checkpoint_name(v_new, STATES_NAME)
    return (entered, v_new), (w, k_to_end, chunk_decay, entered, v_new)


def _walk_backward(kept, cotangents):
    """One reversed scan, a chunk's slices a trip, carrying the
    cotangent of the state a chunk leaves (zeros at the last: its
    leaving state is discarded): autodiff's five products, their
    operands rounded where the forward rounds them.  ``_rounded``'s
    derivative is the identity here; autodiff's of the two casts rounds
    the cotangent to the compute dtype on its way, once a product."""
    _WALK_TRACES.inc(1, **{"pass": "backward"})
    w, k_to_end, chunk_decay, entered, v_new = kept
    dtype = w.dtype

    def step(d_left, of_chunk):
        w_c, k_c, decay, carried, v_c, d_carried, d_v = of_chunk
        d_v = d_v + jnp.einsum("blhk,bhkv->blhv", _rounded(k_c, dtype),
                               d_left)
        d_k = jnp.einsum("blhv,bhkv->blhk", _rounded(v_c, dtype), d_left)
        d_decay = jnp.sum(d_left * carried, axis=(-2, -1))
        d_w = -jnp.einsum("blhv,bhkv->blhk", d_v, _rounded(carried, dtype))
        d_entering = (d_carried + d_left * decay[..., None, None]
                      - jnp.einsum("blhk,blhv->bhkv", _rounded(w_c, dtype),
                                   d_v))
        return d_entering, (d_w.astype(dtype), d_v, d_k.astype(dtype),
                            d_decay)
    _, gradients = lax.scan(
        step, jnp.zeros(entered.shape[1:], jnp.float32),
        (w, k_to_end, chunk_decay, entered, v_new, *cotangents),
        reverse=True)
    return gradients


_walk.defvjp(_walk_forward, _walk_backward)


def _wy_by_xla(k, v, beta, between, to_here):
    """``w`` (``k``'s dtype) and ``u`` (float32) of every chunk's system
    as XLA's operations: ``k`` ``[batch, count, length, heads,
    key_dim]``, ``v`` likewise, ``beta`` ``[batch, count, length,
    heads]`` float32, and of the log-decay's running sum the decay
    ``between`` two positions of a chunk and ``to_here``.  The path of
    every input the kernels do not take, and their oracle."""
    key_dim, length = k.shape[-1], k.shape[2]
    # Position i's correction reads the corrections of the positions
    # j < i of its chunk through beta_i (k_i . k_j) times the decay
    # from j to i.
    beta_h = jnp.transpose(beta, (0, 3, 1, 2))   # [b, h, c, l]
    keys = jnp.einsum("bclhd,bcshd->bhcls", k, k,
                      preferred_element_type=jnp.float32)
    a = jnp.where(jnp.tril(jnp.ones((length, length), bool), -1),
                  keys * between * beta_h[..., None], 0.0)
    # ``T`` is never formed: both right-hand sides go through one
    # triangular solve (the diagonal taken as 1 and not read), the
    # heads before the chunks as ``a`` has them.
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    sides = jnp.concatenate([k32 * (beta[..., None] * to_here),
                             v32 * beta[..., None]], axis=-1)
    solved = solve_triangular(
        a, jnp.transpose(sides, (0, 3, 1, 2, 4)), lower=True,
        unit_diagonal=True)
    w, u = jnp.split(jnp.transpose(solved, (0, 2, 3, 1, 4)), [key_dim],
                     axis=-1)
    return w.astype(k.dtype), u


def _wy_kernels():
    """``ops/pallas_gated_delta.py``, imported where a layer is traced
    as kernels, as ``parallel/moe.py`` imports its own: a process that
    runs no kernel does not pay for Pallas."""
    from . import pallas_gated_delta
    return pallas_gated_delta


def solved_in_vmem(kernels: bool, seq: int, chunk: int, key_dim: int,
                   value_dim: int, dtype) -> bool:
    """The rule: the chunks' systems are the Pallas kernels' where the
    caller found its arrays on one TPU device (``kernels``:
    ``parallel/moe.py`` ``on_one_tpu``) AND the kernels' tiles divide
    the static shapes; XLA's operations everywhere else."""
    return bool(kernels) and _wy_kernels().fits(
        chunks_of(seq, chunk)[1], key_dim, value_dim, dtype)


def _wy_in_vmem(k, v, beta, cum):
    """:func:`_wy_by_xla`'s results from the two Pallas kernels: a
    (head, chunk)'s ``A`` made and solved in VMEM.  ``k`` and ``v`` go
    as they lie (a head's lanes are a block's), ``beta`` and ``cum`` a
    head's chunk along the lanes."""
    batch, count, length, heads, _ = k.shape
    systems = batch * count
    # The kernels take the chunks two at a time: an odd count gets one
    # of zeros (``beta`` = 0: it solves to zeros), cut off again.
    even = lambda t: jnp.pad(t, ((0, systems % 2),) + ((0, 0),) * 2)
    rows = lambda t: even(t.reshape(systems, length, -1))
    narrow = lambda t, order: even(jnp.transpose(t, order).reshape(
        systems, heads, length))
    w, u = _wy_kernels().wy(rows(k), rows(v), narrow(beta, (0, 1, 3, 2)),
                            narrow(cum, (0, 2, 1, 3)), heads)
    return w[:systems].reshape(k.shape), u[:systems].reshape(v.shape)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64,
                        kernels: bool = False):
    """``o`` of the recurrence above, ``[batch, seq, heads, value_dim]``
    in ``v``'s dtype, from ``S = 0``.

    ``q``, ``k``: ``[batch, seq, heads, key_dim]`` (normed and scaled by
    the caller); ``v``: ``[batch, seq, heads, value_dim]``; ``g``:
    ``[batch, seq, heads]``, the log of the decay, at most 0; ``beta``:
    ``[batch, seq, heads]``.  A sequence that the chunk's length does
    not divide is padded at its end (``chunks_of``): a padded position
    has ``g`` = 0 and ``beta`` = 0, so it neither decays the state nor
    writes to it, and its output is cut off.

    ``kernels``: the caller found the layer's arrays on one TPU device
    (``parallel/moe.py`` ``on_one_tpu``), so the chunks' systems are
    made and solved by the Pallas kernels of
    ``ops/pallas_gated_delta.py`` wherever their tiles divide the shapes
    (:func:`solved_in_vmem`); every other input takes XLA's operations,
    and the results are the same to a rounding."""
    batch, seq, heads, key_dim = k.shape
    count, length = chunks_of(seq, chunk)
    pad = count * length - seq
    dtype = v.dtype

    def chunked(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(batch, count, length, *t.shape[2:])
    q, k, v = chunked(q), chunked(k), chunked(v)
    g, beta = (chunked(t.astype(jnp.float32)) for t in (g, beta))

    with jax.named_scope("decay"):
        # The running sum of the log-decay within a chunk (inclusive),
        # heads before positions: [batch, heads, count, length]; and
        # the decay between two positions of a chunk.  Above the
        # diagonal the difference is positive and may overflow: masked
        # before the exponential, so that its gradient is 0 and not nan.
        cum = jnp.cumsum(jnp.transpose(g, (0, 3, 1, 2)), axis=-1)
        lower = jnp.tril(jnp.ones((length, length), bool))
        between = jnp.exp(jnp.where(
            lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        by_position = lambda t: jnp.transpose(t, (0, 2, 3, 1))[..., None]
        to_here = by_position(jnp.exp(cum))          # [b, c, l, h, 1]
        to_end = by_position(jnp.exp(cum[..., -1:] - cum))
        chunk_decay = jnp.exp(cum[..., -1])          # [b, h, c]

    with jax.named_scope("wy"):
        for kernel in ("wy_fwd", "wy_bwd"):
            KERNEL_TRACES.inc(0, kernel=kernel)   # reads 0, not absent
        if solved_in_vmem(kernels, seq, chunk, key_dim, v.shape[-1], dtype):
            w, u = _wy_in_vmem(k, v, beta, cum)
        else:
            w, u = _wy_by_xla(k, v, beta, between, to_here)
        w = checkpoint_name(w, WY_NAME)
        u = checkpoint_name(u, WY_NAME)

    with jax.named_scope("state_scan"):
        # The walk over the chunks: what a chunk writes is its
        # corrections less what the state it is entered with already
        # holds at its keys.
        k_to_end = (k.astype(jnp.float32) * to_end).astype(dtype)

        by_chunk = lambda t: jnp.moveaxis(t, 1, 0)
        entered, v_new = _walk(by_chunk(w), by_chunk(u), by_chunk(k_to_end),
                               jnp.moveaxis(chunk_decay, 2, 0))
        entered, v_new = by_chunk(entered), by_chunk(v_new)

    with jax.named_scope("intra_chunk"):
        # Position l reads what position s <= l of its chunk wrote
        # through (q_l . k_s) times the decay from s to l.
        scores = jnp.einsum("bclhd,bcshd->bhcls", q, k,
                            preferred_element_type=jnp.float32)
        weights = (scores * between).astype(dtype)
        o = jnp.einsum("bhcls,bcshv->bclhv", weights, v_new.astype(dtype),
                       preferred_element_type=jnp.float32)

    with jax.named_scope("state_output"):
        # The entering state as position l sees it: decayed by cum_l.
        o = o + jnp.einsum(
            "bclhk,bchkv->bclhv",
            (q.astype(jnp.float32) * to_here).astype(dtype),
            entered.astype(dtype), preferred_element_type=jnp.float32)

    o = o.reshape(batch, count * length, heads, v.shape[-1])[:, :seq]
    return o.astype(dtype)
