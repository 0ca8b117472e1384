"""The gated delta rule's within-chunk system as two Pallas TPU kernels:
``A`` made, solved and differentiated in VMEM.

``ops/gated_delta.py``'s ``wy`` stage solves, for every head and chunk
of ``L`` positions, the unit lower-triangular system::

    P = k k^T                                   # the compute dtype's product
    A = strictly_lower(beta_i P_ij exp(cum_i - cum_j))
    (I + A) [w | u] = [k beta exp(cum) | v beta]

As XLA's operations that is a dozen float32 passes over ``[heads,
chunks, L, L]`` arrays of 64 lanes padded to 128, a solver that walks
the rows in turn, and two transposes around it.  Here a grid step holds
``CHUNKS`` systems of one head in VMEM and neither ``P``, ``A`` nor the
right-hand sides ever reach HBM.

* ``hvd_gdn_wy_fwd``: ``w`` (the compute dtype) and ``u`` (float32)
  from ``k``, ``v``, ``beta`` and ``cum``.
* ``hvd_gdn_wy_bwd``: the four cotangents from the same operands and
  ``[dw | du]``.  ``A``, ``T = (I + A)^-1`` and the solution are made
  AGAIN in VMEM, so the rule keeps nothing but its own operands; then
  autodiff's products of the lines above, written out: with ``w = (T .
  s) k`` and ``u = (T . beta) v`` (below) ``dT = (dw k^T) . s + (du
  v^T) . beta``, ``dA = -strictly_lower(T^T dT T^T)``, ``dk = (T . s)^T
  dw + (dP + dP^T) k`` with ``dP = dA . beta_i exp(cum_i - cum_j)``,
  ``dv = (T . beta)^T du``, and the sums over rows and columns that are
  ``beta``'s and ``cum``'s.

*Two systems a pass.*  A chunk of 64 positions fills half of a
register's 128 lanes and a quarter of a tile of the matrix unit.  The
kernels take the chunks of a head two at a time, side by side along
the lanes (``[L, 2 L]`` arrays ``[Ma | Mb]``): an elementwise pass
serves both, and a product whose second operand is ``[[Ma, 0], [0,
Mb]]`` (``_Masks.squares``) gives ``[Xa Ma | Xb Mb]`` for the rows of
one.

*The solve* is a blocked forward substitution, the form XLA's own
expander has.  ``T`` comes of the diagonal blocks of ``DIAG`` rows,
inverted by substitution over their rows on the vector unit (every
block's rows a value of its own, a step a static slice of a column and
of a row: no loop, no scratch), and of the blocks below them by
products on the matrix unit, a doubling a level: ``[[T0, 0], [-T1 A10
T0, T1]]``.  Every product of the solve is float32 at full precision,
as the configuration states: ``lax.Precision.HIGHEST`` (six passes of
the matrix unit) between float32 operands; and where one operand IS
bfloat16 (``k``, ``v``, ``dw``) the three passes that are left of the
six, made as ONE product of the other operand's three bfloat16 parts
(``_dot_exact``): every partial product exact, every sum float32's.
The right-hand sides' scales go over to ``T``'s columns for that, ``T
(k . s_i) = (T . s_j) k``, which also keeps ``beta`` and ``cum`` the
ROWS they arrive as; the one place that needs them along the sublanes,
``beta_i exp(cum_i - cum_j)``, turns them by a select and a sum over
the lanes (``_Masks.columns``), and a sum over a system's lanes comes
back to a row the same way (``_Masks.own_sums``).  ``P`` and
the keys' cotangent through it take the compute dtype's operands, as
the einsum they replace.  The Neumann product ``(I - A)(I + A^2)(I +
A^4)...`` stays out: its powers grow where a chunk's keys are alike.

Set-up is paid once: each kernel is ONE module-level function under
``jax.jit`` whose grid and blocks come from static shapes alone, called
in ``ops/pallas_moe.py``'s ``one_trace_context``, so the layers of a
step, its recomputed pass and a process's other programs trace a body
once between them; ``hvd_gdn_kernel_traces{kernel}`` counts the traces.
The bodies are ``lax``'s primitives.

Layouts: ``k``, ``w`` ``[N, L, heads * key_dim]``, ``v``, ``u`` ``[N, L,
heads * value_dim]`` (``N`` = batch x chunks, even: a reshape of what
the operator holds); ``beta``, ``cum`` ``[N, heads, L]`` float32, a
head's ``L`` values along the lanes, laid out a pair of chunks a row
(``_paired``) inside the jitted functions.  ``interpret`` is
``pallas_call``'s.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import KERNEL_TRACES as _TRACES
from .pallas_moe import one_trace_context

LANES = 128
# Rows of a diagonal block, inverted by substitution on the vector unit.
DIAG = 16
# Systems of one head a grid step takes.
CHUNKS = 8

_HIGHEST = lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def fits(length: int, key_dim: int, value_dim: int, dtype) -> bool:
    """Whether the kernels' tiles divide a layer's static shapes: a
    head's keys and values whole tiles of 128 lanes, a chunk a power of
    two of rows that the diagonal blocks divide and one tile of lanes
    holds, in a dtype the bodies are written for."""
    return (key_dim % LANES == 0 and value_dim % LANES == 0
            and DIAG <= length <= LANES and length & (length - 1) == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _dot(a, b, dims=_NN, precision=_HIGHEST):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _float(x):
    return lax.convert_element_type(x, jnp.float32)


def _wide(x, shape):
    """``x`` ``[rows, 1]`` or ``[1, columns]`` over ``shape``."""
    return lax.broadcast_in_dim(x, shape, (0, 1))


def _parts(x):
    """``x`` (float32) as three bfloat16 values whose sum it is."""
    high = lax.convert_element_type(x, jnp.bfloat16)
    rest = x - _float(high)
    middle = lax.convert_element_type(rest, jnp.bfloat16)
    return high, middle, lax.convert_element_type(rest - _float(middle),
                                                  jnp.bfloat16)


def _dot_exact(a, b, dims=_NN):
    """``a`` (float32) times ``b`` at float32's full precision.  Where
    ``b`` IS bfloat16 (``k``, ``v`` and ``dw`` arrive so) the six
    passes of ``HIGHEST`` are three, those of ``b``'s low parts being
    zero: ``a``'s three parts stacked along its free dimension make
    them ONE product of the matrix unit, whose every partial product is
    exact and whose sums are float32's."""
    if b.dtype != jnp.bfloat16:
        return _dot(a, b, dims)
    free = 1 if dims == _TN else 0
    rows = a.shape[free]
    stacked = lax.dot_general(
        lax.concatenate(list(_parts(a)), free), b, (dims, ((), ())),
        preferred_element_type=jnp.float32)
    part = lambda i: lax.slice(stacked, (i * rows, 0),
                               ((i + 1) * rows, stacked.shape[1]))
    return (part(2) + part(1)) + part(0)


class _Masks:
    """What every pair of a grid step shares.  TWO systems lie side by
    side along the lanes, ``[L, 2 L]`` arrays ``[Ma | Mb]``: lane ``n``
    is column ``n % L`` of system ``n // L``, so a pass of the vector
    unit and a row of the matrix unit serve both."""

    def __init__(self, length: int):
        self.length, diag = length, DIAG   # rows of a diagonal block
        self.wide = wide = (length, 2 * length)
        rows = lax.broadcasted_iota(jnp.int32, wide, 0)
        lanes = lax.broadcasted_iota(jnp.int32, wide, 1)
        cols = lax.bitwise_and(lanes, jnp.int32(length - 1))
        self.first = lanes < length              # the first system's lanes
        self.zeros = jnp.zeros(wide, jnp.float32)
        self.eye = rows == cols
        self.identity = lax.select(self.eye, jnp.ones(wide, jnp.float32),
                                   self.zeros)
        self.strict = rows > cols
        block = lambda of, size: lax.shift_right_logical(
            of, jnp.int32(size.bit_length() - 1))
        self.on_diagonal = block(rows, diag) == block(cols, diag)
        # A level of the doubling: the blocks of ``size`` rows below
        # the diagonal of the blocks of twice that.
        self.below = []
        size = diag
        while size < length:
            self.below.append(lax.bitwise_and(
                block(rows, 2 * size) == block(cols, 2 * size),
                block(rows, size) > block(cols, size)))
            size *= 2
        tall = (2 * length, 2 * length)
        self.own = ((lax.broadcasted_iota(jnp.int32, tall, 0) < length)
                    == (lax.broadcasted_iota(jnp.int32, tall, 1) < length))
        self.tall_zeros = jnp.zeros(tall, jnp.float32)

    def squares(self, m):
        """``[[Ma, 0], [0, Mb]]`` ``[2 L, 2 L]`` of ``m`` = ``[Ma |
        Mb]``: as a product's second operand it keeps the systems
        apart, ``[Xa | Xb] squares(m) = [Xa Ma | Xb Mb]``."""
        return lax.select(self.own, lax.concatenate([m, m], 0),
                          self.tall_zeros)

    def fold(self, tall):
        """``[Ma | Mb]`` of a ``[2 L, 2 L]`` product whose diagonal
        squares are ``Ma`` and ``Mb``."""
        length = self.length
        return lax.select(
            self.first, lax.slice(tall, (0, 0), (length, 2 * length)),
            lax.slice(tall, (length, 0), (2 * length, 2 * length)))

    def _columns(self, spread):
        """The sums over each system's own lanes of ``spread``, each
        over its system's lanes again: ``[L, 2 L]``."""
        sums = lambda of: _wide(jnp.sum(of, axis=1, keepdims=True), self.wide)
        return lax.select(
            self.first, sums(lax.select(self.first, spread, self.zeros)),
            sums(lax.select(self.first, self.zeros, spread)))

    def columns(self, row):
        """``row`` ``[1, 2 L]`` = ``[ra | rb]`` along the sublanes:
        ``ra_i`` over the first system's lanes, ``rb_i`` over the
        second's.  A select and a sum, exact."""
        return self._columns(lax.select(self.eye, _wide(row, self.wide),
                                        self.zeros))

    def own_sums(self, m):
        """The sums of ``m`` over each system's own lanes, as a ROW
        ``[1, 2 L]``."""
        return jnp.sum(lax.select(self.eye, self._columns(m), self.zeros),
                       axis=0, keepdims=True)

    def inverse(self, a):
        """``(I + a)^-1`` of both systems' strictly lower ``a``.  The
        diagonal blocks by substitution over their rows, every block's
        rows a value of its own: column ``j`` of a block (a static
        slice of a lane, one a system) times row ``j`` of what is
        solved comes off the rows below it, 15 steps of four
        independent blocks at ``DIAG`` 16.  Then the blocks below them
        by products, doubling: ``[[T0, 0], [-T1 A10 T0, T1]]`` for every
        pair of blocks of both systems at once."""
        diag, length = DIAG, self.length
        of_block = lambda m, b: lax.slice(m, (b * diag, 0),
                                          ((b + 1) * diag, 2 * length))
        inside = lax.select(self.on_diagonal, a, self.zeros)
        first = lax.broadcasted_iota(jnp.int32, (diag, 2 * length),
                                     1) < length
        solved = []
        for b in range(length // diag):
            t, below = of_block(self.identity, b), of_block(inside, b)
            for j in range(diag - 1):
                at = b * diag + j
                column = lax.select(
                    first,
                    _wide(lax.slice(below, (0, at), (diag, at + 1)), t.shape),
                    _wide(lax.slice(below, (0, length + at),
                                    (diag, length + at + 1)), t.shape))
                row = lax.slice(t, (j, 0), (j + 1, 2 * length))
                t = t - column * _wide(row, t.shape)
            solved.append(t)
        t = lax.concatenate(solved, 0)
        for below in self.below:
            left = _dot(lax.select(below, a, self.zeros), self.squares(t))
            t = t - _dot(t, self.squares(left))
        return t


class _Pair:
    """Two chunks of one head: their operands read, ``A`` made and
    solved, and ``w = (T . s) k``, ``u = (T . beta) v``: the right-hand
    sides' row scales ``s = beta exp(cum)`` and ``beta`` taken over to
    ``T``'s columns, where they are ROWS, as the operands hold them."""

    def __init__(self, masks, k_ref, v_ref, beta_ref, cum_ref, head, p):
        wide, zeros = masks.wide, masks.zeros
        self.k, self.v = _read(k_ref, p), _read(v_ref, p)     # [2 L, d]
        self.beta = beta_ref[p, pl.ds(head, 1), :]            # rows [1, 2 L]
        cum = cum_ref[p, pl.ds(head, 1), :]
        gap = masks.columns(cum) - _wide(cum, wide)
        # The decay from j to i; above the diagonal the difference is
        # positive and may overflow: masked before the exponential.
        self.decay = lax.select(masks.strict, lax.exp(lax.select(
            masks.strict, gap, zeros)), zeros)
        self.exact = _HIGHEST if self.k.dtype == jnp.float32 else None
        self.p = masks.fold(_dot(self.k, self.k, _NT, self.exact))
        self.weighed = masks.columns(self.beta) * self.decay
        self.a = self.p * self.weighed
        self.t = masks.inverse(self.a)
        self.to_here = lax.exp(cum)
        self.k_scale = self.beta * self.to_here
        self.tw = masks.squares(self.t * _wide(self.k_scale, wide))
        self.tu = masks.squares(self.t * _wide(self.beta, wide))
        self.w = _dot_exact(self.tw, self.k)                  # [2 L, d]
        self.u = _dot_exact(self.tu, self.v)


def _read(ref, p):
    """The pair's two chunks' blocks, one over the other: ``[2 L, d]``."""
    return lax.concatenate([ref[2 * p], ref[2 * p + 1]], 0)


def _write(ref, p, value):
    """The pair's ``[2 L, d]`` into its two chunks' blocks."""
    length = value.shape[0] // 2
    for i in range(2):
        ref[2 * p + i] = lax.convert_element_type(
            lax.slice(value, (i * length, 0),
                      ((i + 1) * length, value.shape[1])), ref.dtype)


def _fwd_kernel(k_ref, v_ref, beta_ref, cum_ref, w_ref, u_ref):
    masks = _Masks(k_ref.shape[1])
    head = pl.program_id(1)

    def of_pair(p, carry):
        s = _Pair(masks, k_ref, v_ref, beta_ref, cum_ref, head, p)
        _write(w_ref, p, s.w)
        _write(u_ref, p, s.u)
        return carry
    lax.fori_loop(0, k_ref.shape[0] // 2, of_pair, 0)


def _bwd_kernel(k_ref, v_ref, beta_ref, cum_ref, dw_ref, du_ref, dk_ref,
                dv_ref, dbeta_ref, dcum_ref):
    masks = _Masks(k_ref.shape[1])
    head = pl.program_id(1)
    wide = masks.wide

    def of_pair(p, carry):
        s = _Pair(masks, k_ref, v_ref, beta_ref, cum_ref, head, p)
        d_w, d_u = _read(dw_ref, p), _read(du_ref, p)
        # w = tw k and u = tu v: the cotangents of the two scaled
        # inverses, of k and v through them, and (the sums over a
        # column, rows again) of the scales.
        d_tw = masks.fold(_dot(d_w, s.k, _NT, s.exact))
        d_tu = masks.fold(_dot_exact(d_u, s.v, _NT))
        _write(dv_ref, p, _dot(s.tu, d_u, _TN))
        d_scale = jnp.sum(d_tw * s.t, axis=0, keepdims=True)
        d_beta = jnp.sum(d_tu * s.t, axis=0, keepdims=True)
        d_t = d_tw * _wide(s.k_scale, wide) + d_tu * _wide(s.beta, wide)
        # t = (I + a)^-1: da = -t^T dt t^T under the diagonal.
        squares = masks.squares(s.t)
        inner = masks.fold(_dot(squares, masks.squares(d_t), _TN))
        d_a = lax.select(masks.strict, -_dot(inner, squares, _NT),
                         masks.zeros)
        moved = d_a * s.a
        # The keys' cotangent through their square, as the einsum's
        # transpose takes it: operands of the compute dtype.
        d_p = lax.convert_element_type(masks.squares(d_a * s.weighed),
                                       s.k.dtype)
        _write(dk_ref, p,
               _dot_exact(s.tw, d_w, _TN) + _dot(d_p, s.k, _NN, s.exact)
               + _dot(d_p, s.k, _TN, s.exact))
        dbeta_ref[p, pl.ds(head, 1), :] = (
            d_beta + d_scale * s.to_here
            + masks.own_sums(d_a * s.p * s.decay))
        dcum_ref[p, pl.ds(head, 1), :] = (
            d_scale * s.k_scale + masks.own_sums(moved)
            - jnp.sum(moved, axis=0, keepdims=True))
        return carry
    lax.fori_loop(0, k_ref.shape[0] // 2, of_pair, 0)


def _specs(k, v, heads: int):
    """The grid (groups of chunks, heads) and the blocks of a head's
    ``[chunks, L, width]`` operands and of all heads' ``beta``-like
    ``[chunks / 2, heads, 2 L]`` ones, which are fetched once a group."""
    count, length, _ = k.shape
    chunks = CHUNKS
    while count % chunks:
        chunks //= 2
    wide = lambda t: pl.BlockSpec((chunks, length, t.shape[2] // heads),
                                  lambda i, h: (i, 0, h))
    narrow = pl.BlockSpec((chunks // 2, heads, 2 * length),
                          lambda i, h: (i, 0, 0))
    return (count // chunks, heads), wide(k), wide(v), narrow


def _paired(t, heads: int):
    """``[N, heads, L]`` as ``[N / 2, heads, 2 L]``: a pair of chunks
    side by side along the lanes, as the kernels hold it."""
    count, _, length = t.shape
    return jnp.transpose(t.reshape(count // 2, 2, heads, length),
                         (0, 2, 1, 3)).reshape(count // 2, heads, 2 * length)


def _unpaired(t, heads: int):
    pairs, _, both = t.shape
    return jnp.transpose(t.reshape(pairs, heads, 2, both // 2),
                         (0, 2, 1, 3)).reshape(2 * pairs, heads, both // 2)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def wy_fwd(k, v, beta, cum, *, heads, interpret=False):
    """``w`` ``[N, L, heads * key_dim]`` in ``k``'s dtype and ``u``
    ``[N, L, heads * value_dim]`` float32 of every (chunk, head); ``N``
    even."""
    _TRACES.inc(1, kernel="wy_fwd")
    grid, of_k, of_v, narrow = _specs(k, v, heads)
    return _call(
        _fwd_kernel, "hvd_gdn_wy_fwd", grid,
        [of_k, of_v, narrow, narrow], [of_k, of_v],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret)(k, v, _paired(beta, heads), _paired(cum, heads))


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def wy_bwd(k, v, beta, cum, dw, du, *, heads, interpret=False):
    """The cotangents of :func:`wy_fwd`'s four operands, in their
    shapes and dtypes, from those of ``w`` and ``u``."""
    _TRACES.inc(1, kernel="wy_bwd")
    grid, of_k, of_v, narrow = _specs(k, v, heads)
    paired = jax.ShapeDtypeStruct(
        (k.shape[0] // 2, heads, 2 * k.shape[1]), jnp.float32)
    d_k, d_v, d_beta, d_cum = _call(
        _bwd_kernel, "hvd_gdn_wy_bwd", grid,
        [of_k, of_v, narrow, narrow, of_k, of_v],
        [of_k, of_v, narrow, narrow],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype), paired, paired],
        interpret)(k, v, _paired(beta, heads), _paired(cum, heads), dw, du)
    return d_k, d_v, _unpaired(d_beta, heads), _unpaired(d_cum, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def wy(k, v, beta, cum, heads):
    """``(w, u)`` of the systems above through the two kernels."""
    with one_trace_context():
        return wy_fwd(k, v, beta, cum, heads=heads)


def _wy_vjp_fwd(k, v, beta, cum, heads):
    return wy(k, v, beta, cum, heads), (k, v, beta, cum)


def _wy_vjp_bwd(heads, kept, cotangents):
    with one_trace_context():
        return tuple(wy_bwd(*kept, *cotangents, heads=heads))


wy.defvjp(_wy_vjp_fwd, _wy_vjp_bwd)
