"""The routed experts' buffer-side passes and grouped products as
Pallas TPU kernels that stop where the pairs end.

``parallel/moe.py`` ``routed_experts`` sorts the (token, expert) pairs
whose expert is held into a buffer as long as the routing can fill it;
the pairs held are a prefix, and their count ``n`` is known on the
device.  XLA's gathers and elementwise passes over such a buffer cost
the same whatever ``n`` is.  These kernels take ``n`` as a prefetched
scalar and do work only for blocks of rows that hold a pair; what they
leave past the pairs is whatever the memory held, which nothing reads
into a result.

* ``hvd_moe_pack_rows``: rows ``[N, D]`` as 32-bit words, one row a
  whole number of (8, 128) tiles (``[N * S, 128]`` uint32, ``S``
  sublanes a row): what a DMA can address by row.  A row of a
  ``[N, D]`` array is no unit of the tiled HBM layout (Mosaic refuses
  a slice of one row of eight), and a bfloat16 row shares its words
  with its neighbour.  Two bfloat16 halves of a row share a word:
  column ``c`` the low half, column ``c + D / 2`` the high half.  The
  token side, where every row is wanted, is packed by XLA's own
  operations (:func:`packed_by_xla`): no count stops that pass, and it
  is one Pallas body fewer in every program.
* ``hvd_moe_rows_of_tokens``: ``rows[r] = x[token[r]]`` for ``r < n``:
  one DMA a row, HBM to VMEM, ``IN_FLIGHT`` at a time; the block is
  unpacked into the output, whose block index stops at the last block
  that holds a pair, so nothing is written back past it.
* ``hvd_moe_tokens_of_rows``: ``y[t] = sum of out[row] over t's pairs
  held``, float32: DMAs for the pairs held only (a token's rows come
  listed, those held first, with their count), summed a plane of
  pairs at a time under a select by the count, never a product: a
  slot no DMA wrote holds anything.
* ``hvd_moe_add_rows``: the sum of the two cotangents the rows get
  (two products read them), written over the first: what autodiff's
  ``add_any`` does over the whole buffer.
* ``hvd_moe_gated``, ``hvd_moe_gated_bwd``: ``silu(a) * b * gate``
  over ``[R, F]`` and its backward, float32 throughout and rounded
  once, blocks past the pairs skipped.
* ``hvd_moe_grouped_rows``, ``hvd_moe_grouped_rows_t``,
  ``hvd_moe_grouped_weights``: the grouped product ``out[r] = lhs[r] @
  W[e(r)]`` and its two transposes (what ``lax.ragged_dot`` and its VJP
  compute, in its arithmetic), over the (row tile of ``GROUPED_TILE``,
  group) pairs that hold a row: :class:`Walk`, made once a layer from
  the groups' sizes and prefetched.  A tile two groups share is visited
  once for each with the other's rows masked, a group's weights are
  fetched once however many tiles it has (copied into VMEM while the
  group before it is computed), the grid is as long as the walk (a
  bound read on the device: an empty step costs 0.1 us and a buffer a
  sixteenth full would make 600 of them a call), and every tile touched
  is written whole, zeros in the rows that belong to no group.

Set-up is paid once, and kept small.  Each kernel is reached through
ONE module-level function under ``jax.jit`` whose block sizes and grid
come from static shapes alone: JAX keeps a jitted function's jaxpr by
function object and argument types, so the layers of a step, and the
programs of a process, trace a body once between them (the callers
enter :func:`one_trace_context`, since the tracing context is part of
that key), and a program lowers a body once however many layers call
it.  ``hvd_moe_kernel_traces{kernel}`` counts the traces of
each body (it is incremented inside the jitted function, so a call that
finds the jaxpr kept counts nothing); ``hvd_moe_kernel_block_rows
{kernel}`` is the rows a grid step of each takes.  What a trace and a
lowering cost is Python's time by equation, so the bodies and the index
maps are written in ``lax``'s primitives (a ``jnp`` wrapper is a jitted
function of its own, traced and lowered where it stands) and the block
index's bound is computed once outside the kernel and prefetched: a
sandbox lowering of ``hvd_moe_add_rows`` took 23 ms with ``jnp``'s
``minimum`` and ``//`` in three index maps and takes 11 without.  PR
41's kernels were refused for the seconds their traces and lowerings
cost a start (``PERF.md``, section 6): what is traced where is part of
this module's contract, and ``tests/test_pallas_moe.py`` holds it.

``interpret`` is ``pallas_call``'s: the CPU tests run the bodies with
``True`` (pure JAX, which ``jax.checkpoint`` takes) or with
``pltpu.InterpretParams()`` (the TPU interpreter: memory spaces, DMAs
and semaphores simulated, uninitialised memory nan).
"""

import contextlib
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import metrics

LANES, SUBLANES = 128, 8
# Rows of the buffer a grid step takes, tokens a grid step of the token
# side takes, and row copies in flight: picked on a TPU v5 lite at the
# three sparse cells' shapes (``CHANGES.md``, PR 41, has the table; the
# scalar core's issue of 4 KiB copies bounds the gathers, and neither
# the block nor the copies in flight moved them).
ROW_BLOCK = 512
TOKEN_BLOCK = 128
IN_FLIGHT = 16
# XLA lays a vector of int32 out in tiles of 1024, and a block of one
# in SMEM is whole tiles.
SMEM_TILE = 1024
# Elements of a block of the gated product's operands: its backward
# holds seven such blocks twice over and float32 values of them beside,
# inside the 16 MiB of VMEM a kernel has where it asks for no more.
GATED_BLOCK_ELEMENTS = 1 << 18

_TRACES = metrics.gauge(
    "hvd_moe_kernel_traces",
    "Traces of the routed experts' Pallas kernels this process made, by "
    "kernel (incremented inside each kernel's jitted function, so it counts "
    "traces of the body, not calls: one a distinct shape and configuration "
    "context, however many layers and programs call it)")
_BLOCK_ROWS = metrics.gauge(
    "hvd_moe_kernel_block_rows",
    "Rows a grid step of each of the routed experts' Pallas kernels takes "
    "(tokens for tokens_of_rows), by kernel; a block that starts past the "
    "pairs held does no work (set when the kernel is traced)")


def _traced(kernel: str, block: int):
    """Put a trace of ``kernel``'s body on record."""
    _TRACES.inc(1, kernel=kernel)
    _BLOCK_ROWS.set(block, kernel=kernel)


@contextlib.contextmanager
def one_trace_context():
    """The context the kernels' jitted functions are called in, so that
    one jaxpr serves every call: JAX keys a trace by the tracing
    context too, and two parts of it vary between the programs of a
    process with nothing in them for a kernel.  A differentiated custom
    VJP's rules run under an EMPTY abstract mesh where a forward pass
    runs under none (entered here wherever no mesh's axes are in
    scope); and a reference check traces the program under a default
    matmul precision of its own (set to none here: no kernel, and
    nothing under the jitted functions, holds a matrix product)."""
    mesh = jax.sharding.get_abstract_mesh()
    with contextlib.ExitStack() as stack:
        if mesh.empty:
            stack.enter_context(jax.sharding.use_abstract_mesh(mesh))
        stack.enter_context(jax.default_matmul_precision(None))
        yield


def row_sublanes(width: int, dtype) -> int:
    """Sublanes of 128 words one packed row of ``width`` takes; 0 where
    a row is no whole number of (8, 128) tiles of words, and the
    kernels do not apply."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return 0
    words = width * dtype.itemsize // 4
    return (words // LANES
            if words % (LANES * SUBLANES) == 0 and words > 0 else 0)


def _extent(n, block: int):
    """``[n, last]`` int32, what a pass over blocks of ``block`` rows
    prefetches: the rows that hold a pair, and the index of the last
    block that holds one (0 where none does).  Made here, by XLA, so
    that an index map is one ``min``."""
    n = jnp.asarray(n, jnp.int32).reshape(())
    last = lax.max(lax.div(n + (block - 1), jnp.int32(block)) - 1,
                   jnp.int32(0))
    return jnp.stack([n, last])


def _no_further(i, extent_ref):
    """A block index that stops at the last block with a pair: a block
    past it is neither fetched nor written back (Pallas moves a block
    when its index changes)."""
    return lax.min(i, extent_ref[1])


def _starts_before_pairs(extent_ref, block: int):
    return pl.program_id(0) * block < extent_ref[0]


def _select_rows(keep, value):
    """``value`` ``[rows, width]`` where ``keep`` ``[rows, 1]``, else
    zeros: a select, never a product (what is dropped may be nan)."""
    return lax.select(lax.broadcast_in_dim(keep, value.shape, (0, 1)),
                      value, lax.full_like(value, 0))


def _scalars(values, a_step: int, index=lambda i, *_: i):
    """``values`` (a vector of int32 of which a grid step reads
    ``a_step``) padded to whole SMEM blocks, its BlockSpec, and how many
    steps share a block: step ``i`` reads from ``(i % shared) *
    a_step`` of block ``index(i) // shared``."""
    shared = SMEM_TILE // math.gcd(a_step, SMEM_TILE)
    size = shared * a_step
    values = jnp.pad(values, (0, -values.shape[0] % size))
    spec = pl.BlockSpec(
        (size,), lambda *ids: (lax.div(index(*ids), jnp.int32(shared)),),
        memory_space=pltpu.SMEM)
    return values, spec, shared


def _first_of_step(shared: int, a_step: int):
    """Where this grid step's scalars start in its SMEM block."""
    return lax.rem(pl.program_id(0), jnp.int32(shared)) * a_step


def _bits(x):
    return lax.bitcast_convert_type(lax.convert_element_type(x, jnp.float32),
                                    jnp.uint32)


def _words_of(chunks):
    """One uint32 word for each position of the halves' chunks: a
    float32 is its bits, two bfloat16 share a word, low and high."""
    if len(chunks) == 1:
        return _bits(chunks[0])
    low, high = (_bits(c) for c in chunks)
    return lax.bitwise_or(
        lax.shift_right_logical(low, lax.full_like(low, 16)),
        lax.bitwise_and(high, lax.full_like(high, 0xFFFF0000)))


def _floats_of(words, halves: int):
    """The float32 values a word holds, one a half."""
    as_float = functools.partial(lax.bitcast_convert_type,
                                 new_dtype=jnp.float32)
    if halves == 1:
        return [as_float(words)]
    return [as_float(lax.shift_left(words, lax.full_like(words, 16))),
            as_float(lax.bitwise_and(words,
                                     lax.full_like(words, 0xFFFF0000)))]


def _pack_kernel(extent_ref, x_ref, o_ref, *, block, sub, halves):
    @pl.when(_starts_before_pairs(extent_ref, block))
    def _():
        half = sub * LANES
        words = _words_of([x_ref[:, pl.ds(h * half, half)]
                           for h in range(halves)])
        for c in range(sub):
            o_ref[pl.ds(c, block, stride=sub), :] = words[
                :, c * LANES:(c + 1) * LANES]


def _unpacked(buf, first, block: int, sub: int, halves: int):
    """The float32 halves ``[block, sub * 128]`` of the ``block`` packed
    rows of ``buf`` that start at its row ``first``: a row's ``sub``
    sublanes lie ``sub`` apart, and side by side they are its words."""
    words = lax.concatenate(
        [buf[pl.ds(first + c, block, stride=sub), :] for c in range(sub)], 1)
    return _floats_of(words, halves)


def _rows_call(kernel, name, n, ins, outs, block, interpret, **options):
    """A pass over arrays of ``R`` rows a block of rows at a time; a
    block that starts past the ``n`` rows that hold a pair is neither
    fetched nor computed nor written back.  ``outs`` are ``(rows a
    row of the input, width, dtype)``."""
    rows = ins[0].shape[0]

    def spec(per_row, width):
        return pl.BlockSpec(
            (block * per_row, width),
            lambda i, extent_ref: (_no_further(i, extent_ref), 0))
    return pl.pallas_call(
        functools.partial(kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(rows, block),),
            in_specs=[spec(1, x.shape[1]) for x in ins],
            out_specs=[spec(per_row, width) for per_row, width, _ in outs]),
        out_shape=[jax.ShapeDtypeStruct((rows * per_row, width), dtype)
                   for per_row, width, dtype in outs],
        interpret=interpret, name=name, **options)(_extent(n, block), *ins)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pack_rows(x, n, *, block=ROW_BLOCK, interpret=False):
    """``[N * S, 128]`` uint32: the first ``n`` rows of ``x`` ``[N, D]``
    as words, a row ``S`` sublanes; past the block that holds row
    ``n - 1`` whatever the memory held."""
    sub = row_sublanes(x.shape[1], x.dtype)
    block = min(block, x.shape[0])
    _traced("pack_rows", block)
    tiles, = _rows_call(
        functools.partial(_pack_kernel, sub=sub,
                          halves=4 // x.dtype.itemsize),
        "hvd_moe_pack_rows", n, (x,), [(sub, LANES, jnp.uint32)],
        block, interpret)
    return tiles


def packed_by_xla(x):
    """:func:`pack_rows` of every row of ``x``, as XLA's own operations:
    the words made elementwise and a reshape that XLA lays out.  For the
    token side, where every row is wanted and no count stops the pass
    (on the chip as fast as the kernel's packing there or faster, and a
    body fewer in every program: ``CHANGES.md``, PR 42)."""
    sub = row_sublanes(x.shape[1], x.dtype)
    half = sub * LANES
    words = _words_of([x[:, h * half:(h + 1) * half]
                       for h in range(4 // x.dtype.itemsize)])
    return words.reshape(x.shape[0] * sub, LANES)


def _add_kernel(extent_ref, a_ref, b_ref, o_ref, *, block):
    @pl.when(_starts_before_pairs(extent_ref, block))
    def _():
        o_ref[...] = lax.convert_element_type(
            lax.convert_element_type(a_ref[...], jnp.float32)
            + lax.convert_element_type(b_ref[...], jnp.float32),
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def add_rows(a, b, n, *, block=ROW_BLOCK, interpret=False):
    """``a + b`` for the first ``n`` rows of two ``[R, D]`` arrays, as
    XLA adds two cotangents (in float32, rounded to their type), written
    over ``a``: no third buffer, and no row past the pairs."""
    block = min(block, a.shape[0])
    _traced("add_rows", block)
    out, = _rows_call(
        _add_kernel, "hvd_moe_add_rows", n, (a, b),
        [(1, a.shape[1], a.dtype)], block, interpret,
        input_output_aliases={1: 0})
    return out


def _copy_loop(count, start, wait, in_flight: int):
    """``count`` copies, at most ``in_flight`` of them started and not
    waited for; every copy is of one size and signals one semaphore,
    so a wait is for any one of them."""
    window = lax.min(count, jnp.int32(in_flight))

    def step(body):
        return lambda r, carry: (body(r), carry)[1]
    lax.fori_loop(0, window, step(start), 0)
    lax.fori_loop(window, count, step(lambda r: (wait(), start(r))), 0)
    lax.fori_loop(0, window, step(lambda r: wait()), 0)


def _rows_of_tokens_kernel(extent_ref, token_ref, tiles_ref, o_ref, buf, sem,
                           *, block, sub, halves, in_flight, shared):
    held = lax.min(extent_ref[0] - pl.program_id(0) * block,
                   jnp.int32(block))
    first = _first_of_step(shared, block)

    def copy(source, row):
        return pltpu.make_async_copy(
            tiles_ref.at[pl.ds(pl.multiple_of(source * sub, SUBLANES), sub)],
            buf.at[pl.ds(pl.multiple_of(row * sub, SUBLANES), sub)], sem)

    @pl.when(held > 0)
    def _():
        _copy_loop(held, lambda r: copy(token_ref[first + r], r).start(),
                   lambda: copy(0, 0).wait(), in_flight)
        # What is left of the last block past the pairs is what the
        # scratch held: unspecified, as every row after it.
        half = sub * LANES
        for h, value in enumerate(_unpacked(buf, 0, block, sub, halves)):
            o_ref[:, pl.ds(h * half, half)] = lax.convert_element_type(
                value, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block", "in_flight", "interpret"))
def rows_of_tokens(x, token, n, *, block=ROW_BLOCK, in_flight=IN_FLIGHT,
                   interpret=False):
    """``[R, D]``: ``x[token[r]]`` for ``r < n``, ``x`` ``[T, D]`` and
    ``token`` ``[R]``; from row ``n`` on whatever the memory held."""
    rows, width = token.shape[0], x.shape[1]
    sub = row_sublanes(width, x.dtype)
    halves = 4 // x.dtype.itemsize
    block = min(block, rows)
    _traced("rows_of_tokens", block)
    tiles = packed_by_xla(x)
    token, token_spec, shared = _scalars(
        token.astype(jnp.int32), block, _no_further)
    return pl.pallas_call(
        functools.partial(_rows_of_tokens_kernel, block=block, sub=sub,
                          halves=halves, in_flight=in_flight, shared=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(rows, block),),
            in_specs=[token_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (block, width),
                lambda i, extent_ref: (_no_further(i, extent_ref), 0)),
            scratch_shapes=[pltpu.VMEM((block * sub, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        interpret=interpret, name="hvd_moe_rows_of_tokens")(
            _extent(n, block), token, tiles)


def _tokens_of_rows_kernel(count_ref, place_ref, counts_ref, tiles_ref,
                           o_ref, buf, acc, sem, *, block, top_k, sub,
                           halves, in_flight, shared):
    first = _first_of_step(shared, block)

    def copy(source, slot):
        return pltpu.make_async_copy(
            tiles_ref.at[pl.ds(pl.multiple_of(source * sub, SUBLANES), sub)],
            buf.at[pl.ds(pl.multiple_of(slot * sub, SUBLANES), sub)], sem)

    # A token's pairs held are started as they come and waited for
    # once the window is full: the copies of a block are one stream.
    def of_token(t, carry):
        def of_pair(j, started):
            @pl.when(started >= in_flight)
            def _():
                copy(0, 0).wait()
            copy(place_ref[(first + t) * top_k + j], j * block + t).start()
            return started + 1
        held = count_ref[first + t]
        return (lax.fori_loop(0, held, of_pair, carry[0]),
                lax.max(carry[1], held))
    started, most = lax.fori_loop(0, block, of_token,
                                  (jnp.int32(0), jnp.int32(0)))
    lax.fori_loop(0, lax.min(started, jnp.int32(in_flight)),
                  lambda r, carry: (copy(0, 0).wait(), carry)[1], 0)

    acc[...] = jnp.zeros(acc.shape, acc.dtype)
    half = sub * LANES

    def of_plane(j, carry):
        # A select by the count, never a product: a slot no copy wrote
        # holds anything.
        keep = counts_ref[...] > j
        first = pl.multiple_of(j * (block * sub), SUBLANES)
        for h, value in enumerate(_unpacked(buf, first, block, sub, halves)):
            lanes = pl.ds(h * half, half)
            acc[:, lanes] = acc[:, lanes] + _select_rows(keep, value)
        return carry
    lax.fori_loop(0, most, of_plane, 0)
    o_ref[...] = lax.convert_element_type(acc[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block", "row_block", "in_flight", "interpret"))
def tokens_of_rows(out, place, count, n, *, block=TOKEN_BLOCK,
                   row_block=ROW_BLOCK, in_flight=IN_FLIGHT,
                   interpret=False):
    """``[T, D]``: for each token the float32 sum of its pairs' rows of
    ``out`` ``[R, D]``, rounded to ``out``'s type.  ``place`` ``[T,
    top_k]`` lists the rows of a token's pairs held, those first, and
    ``count`` ``[T]`` says how many they are; ``n`` is their sum, the
    rows of ``out`` that hold a pair."""
    tokens, top_k = place.shape
    tiles = pack_rows(out, n, block=row_block, interpret=interpret)
    width = out.shape[1]
    sub = row_sublanes(width, out.dtype)
    halves = 4 // out.dtype.itemsize
    block = min(block, tokens)
    _traced("tokens_of_rows", block)
    count = count.astype(jnp.int32)
    # The steps that share an SMEM block of counts share one of places
    # (whole tiles of counts hold whole tiles of places).
    counts, count_spec, shared = _scalars(count, block)
    places, place_spec, _ = _scalars(
        place.astype(jnp.int32).reshape(-1), shared * block * top_k,
        lambda i: lax.div(i, jnp.int32(shared)))
    return pl.pallas_call(
        functools.partial(_tokens_of_rows_kernel, block=block, top_k=top_k,
                          sub=sub, halves=halves, in_flight=in_flight,
                          shared=shared),
        grid=(pl.cdiv(tokens, block),),
        in_specs=[
            count_spec, place_spec,
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, width), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((top_k * block * sub, LANES), jnp.uint32),
            pltpu.VMEM((block, width), jnp.float32),
            pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((tokens, width), out.dtype),
        interpret=interpret, name="hvd_moe_tokens_of_rows")(
            counts, places, count[:, None], tiles)


def _float(ref):
    return lax.convert_element_type(ref[...], jnp.float32)


def _gated_kernel(extent_ref, a_ref, b_ref, gate_ref, o_ref, *, block):
    @pl.when(_starts_before_pairs(extent_ref, block))
    def _():
        a = _float(a_ref)
        gated = a * lax.logistic(a) * _float(b_ref) * gate_ref[...]
        o_ref[...] = lax.convert_element_type(gated, o_ref.dtype)


def _gated_bwd_kernel(extent_ref, a_ref, b_ref, gate_ref, d_ref, da_ref,
                      db_ref, dgate_ref, *, block):
    @pl.when(_starts_before_pairs(extent_ref, block))
    def _():
        a, b, d = _float(a_ref), _float(b_ref), _float(d_ref)
        sigmoid = lax.logistic(a)
        silu = a * sigmoid
        weighed = d * gate_ref[...]
        da_ref[...] = lax.convert_element_type(
            weighed * b * (sigmoid + silu * (1.0 - sigmoid)), da_ref.dtype)
        db_ref[...] = lax.convert_element_type(weighed * silu, db_ref.dtype)
        dgate_ref[...] = jnp.sum(d * (silu * b), axis=-1, keepdims=True)


def gated_block(rows: int, width: int) -> int:
    """Rows a grid step of the gated product takes: ``ROW_BLOCK`` at a
    width of 512, halved as the width doubles."""
    block = max(SUBLANES, GATED_BLOCK_ELEMENTS // width)
    return min(rows, ROW_BLOCK, 1 << (block.bit_length() - 1))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gated(a, b, row_gate, n, *, block=None, interpret=False):
    """``silu(a) * b * row_gate`` for the first ``n`` rows of ``a`` and
    ``b`` ``[R, F]``, computed in float32 and rounded once; a row past
    the last block that holds a pair is what the memory held, one
    inside it what its operands there give."""
    block = block or gated_block(*a.shape)
    _traced("gated", block)
    out, = _rows_call(
        _gated_kernel, "hvd_moe_gated", n,
        (a, b, row_gate.astype(jnp.float32)[:, None]),
        [(1, a.shape[1], a.dtype)], block, interpret)
    return out


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gated_bwd(a, b, row_gate, d_gated, n, *, block=None,
              interpret=False):
    """The cotangents of :func:`gated`'s ``a``, ``b`` and ``row_gate``
    (float32 ``[R]``) for the first ``n`` rows, a row from its own
    operands alone; past them as :func:`gated` leaves its rows."""
    wide = (1, a.shape[1], a.dtype)
    block = block or gated_block(*a.shape)
    _traced("gated_bwd", block)
    d_a, d_b, d_gate = _rows_call(
        _gated_bwd_kernel, "hvd_moe_gated_bwd", n,
        (a, b, row_gate.astype(jnp.float32)[:, None], d_gated),
        [wide, wide, (1, 1, jnp.float32)], block, interpret)
    return d_a, d_b, d_gate[:, 0]


# The grouped products.  ``lax.ragged_dot``'s kernel on the TPU walks row
# tiles of 512 whatever the groups hold; an expert here gets 160 to 1024
# rows on average and 59 to 591 by the seed and layer, so most of a tile
# of 512 is another group's rows or none's.  These three walk tiles of
# ``GROUPED_TILE`` rows, the best at every fill the benchmark's four
# sparse cells have and at 2048 and 4096 rows an expert (``PERF.md``,
# PR 48).
GROUPED_TILE = 128
# Elements of a group's weights the row kernels hold (twice: this
# group's and the next one's) and of the float32 accumulator the
# weights' kernel holds beside its output block: the columns are one
# block where that fits, else the largest whole number of lanes that
# divides them and does.
GROUPED_BLOCK_ELEMENTS = 3 << 20
# What the three ask of VMEM at the widest cell (LFM2's 2048 x 1536: two
# blocks of 6 MiB, the rows' tiles and the product beside them), past
# the 16 MiB a kernel has where it asks for no more.
GROUPED_VMEM_BYTES = 48 << 20


class Walk(NamedTuple):
    """What the grouped kernels prefetch: the (row tile, group) pairs a
    layer's three products and their six transposes visit, in order,
    made once a layer from ``group_sizes`` on the device
    (:func:`grouped_walk`).  A group that holds no row is visited once
    all the same (its weights' cotangent is zeros, which something has
    to write)."""
    bounds: jax.Array   # [held + 1] int32: the row a group starts at
    group: jax.Array    # [steps' bound] int32: the group of a step
    tile: jax.Array     # [steps' bound] int32: its row tile
    steps: jax.Array    # [1] int32: the steps there are: the grid's length


def grouped_tile(rows: int) -> int:
    """Rows a grid step of the grouped kernels takes, of a buffer of
    ``rows``."""
    return min(GROUPED_TILE, rows)


def grouped_steps(rows: int, held: int, tile: int) -> int:
    """The most steps a walk has: every tile once and once more for each
    group that starts inside one."""
    return rows // tile + held - 1


def _tile_span(group_sizes, tile: int):
    """``(sizes, ends, first, last)``: each group's rows, the row it
    ends at, and the tiles its first and its last row lie in (of a
    group with no row: the tile it would start in, and one before)."""
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    ends = lax.cumsum(sizes)
    return (sizes, ends, lax.div(ends - sizes, jnp.int32(tile)),
            lax.div(ends - 1, jnp.int32(tile)))


@functools.partial(jax.jit, static_argnames=("rows", "tile"))
def grouped_walk(group_sizes, rows, tile=None):
    """The :class:`Walk` over a buffer of ``rows`` sorted by group,
    ``group_sizes`` ``[held]`` rows each: group ``g`` visits the tiles
    from the one its first row lies in to the one its last row lies in
    (one tile where it has no row), so a tile two groups share is
    visited once for each.  Past the last step the lists name the
    last's tile and group again (a step may look one ahead)."""
    tile = tile or grouped_tile(rows)
    held, tiles = group_sizes.shape[0], rows // tile
    sizes, ends, first, last = _tile_span(group_sizes, tile)
    first = lax.min(first, jnp.int32(tiles - 1))
    last = lax.select(sizes > 0, last, first)
    visits = last - first + 1
    visited = lax.cumsum(visits)
    steps = visited[held - 1:]
    at = lax.min(lax.iota(jnp.int32, grouped_steps(rows, held, tile)),
                 steps - 1)
    group = lax.reduce(
        lax.convert_element_type(
            lax.le(lax.broadcast_in_dim(visited, (at.shape[0], held), (1,)),
                   lax.broadcast_in_dim(at, (at.shape[0], held), (0,))),
            jnp.int32),
        jnp.int32(0), lax.add, (1,))
    tile_of = (jnp.take(first, group)
               + at - jnp.take(visited - visits, group))
    bounds = lax.concatenate([jnp.zeros(1, jnp.int32), ends], 0)
    return Walk(bounds, group, tile_of, steps)


def grouped_tile_fill(group_sizes, rows: int, tile: Optional[int] = None):
    """Rows that hold a pair over the rows of the tiles the grouped
    kernels walk for them (a tile two groups share counted once for
    each, a group with no row not at all): what
    ``hvd_moe_grouped_tile_fill{layer}`` reads.  1 where no tile is
    walked."""
    tile = tile or grouped_tile(rows)
    sizes, _, first, last = _tile_span(jnp.asarray(group_sizes), tile)
    walked = jnp.where(sizes > 0, last - first + 1, 0).sum() * tile
    return jnp.where(walked > 0, sizes.sum() / jnp.maximum(walked, 1), 1.0)


def _columns(columns: int, other: int, elements: int) -> int:
    """The columns a block takes: all of them where a block of ``other``
    rows of them has at most ``elements``, else the largest whole number
    of lanes that divides them and has."""
    fits = [c for c in range(LANES, columns + 1, LANES)
            if columns % c == 0 and c * other <= elements]
    return fits[-1] if fits else LANES


def _rows_of_group(bounds_ref, group, tile_of, shape, tile: int):
    """Which rows of the tile ``tile_of`` lie in ``group``, over
    ``shape`` ``[tile, width]``."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0) + tile_of * tile
    return lax.bitwise_and(lax.ge(row, bounds_ref[group]),
                           lax.lt(row, bounds_ref[group + 1]))


def _changes_at(ref, step):
    """Whether ``ref[step]`` is the first step's or another than the
    step before's."""
    return lax.bitwise_or(step == 0, ref[lax.max(step - 1, 0)] != ref[step])


def _some_rows(bounds_ref, group, tile_of, tile: int):
    """Whether any row of the tile ``tile_of`` lies in ``group``."""
    return lax.lt(lax.max(bounds_ref[group], tile_of * tile),
                  lax.min(bounds_ref[group + 1], (tile_of + 1) * tile))


def _grouped_rows_kernel(bounds_ref, group_ref, tile_ref, steps_ref, lhs_ref,
                         w_ref, o_ref, w_buf, sem, *, tile, transposed):
    column, step = pl.program_id(0), pl.program_id(1)
    group, tile_of = group_ref[step], tile_ref[step]
    # The weights stay in HBM and a group's are copied while the group
    # BEFORE it is computed: the pipeline's own prefetch starts a step
    # ahead, and a step is shorter than the copy (4 us for 8 at LFM2's
    # 2048 x 1536).  Every group is visited, in order, so the group
    # after ``g`` is ``g + 1`` and its slot the other one.
    held, block = w_ref.shape[0], w_buf.shape[1 if transposed else 2]
    slot = lax.rem(group, 2)

    def copy(g, slot):
        columns = pl.ds(column * block, block)
        source = (w_ref.at[g, columns, :] if transposed
                  else w_ref.at[g, :, columns])
        return pltpu.make_async_copy(source, w_buf.at[slot], sem.at[slot])

    @pl.when(step == 0)
    def _():
        copy(group, slot).start()

    @pl.when(_changes_at(group_ref, step))
    def _():
        copy(group, slot).wait()

        @pl.when(group + 1 < held)
        def _():
            copy(group + 1, 1 - slot).start()
    # A tile is written whole the first time it is visited, zeros in the
    # rows that are not this group's: another group's are written at its
    # own visit, the next, and the rows past the last group's end stay
    # zeros.  A visit that finds no row of its group (a group with none)
    # computes nothing.
    fresh = _changes_at(tile_ref, step)
    some = _some_rows(bounds_ref, group, tile_of, tile)

    @pl.when(some)
    def _():
        product = lax.dot_general(
            lhs_ref[...], w_buf[slot],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        mine = _rows_of_group(bounds_ref, group, tile_of, product.shape, tile)

        @pl.when(fresh)
        def _():
            o_ref[...] = lax.convert_element_type(
                lax.select(mine, product, lax.full_like(product, 0)),
                o_ref.dtype)

        @pl.when(lax.bitwise_not(fresh))
        def _():
            o_ref[...] = lax.convert_element_type(
                lax.select(mine, product, _float(o_ref)), o_ref.dtype)

    @pl.when(lax.bitwise_and(lax.bitwise_not(some), fresh))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _grouped_rows(name, lhs, weights, walk, tile, transposed, interpret):
    rows = lhs.shape[0]
    tile = tile or grouped_tile(rows)
    contracted = weights.shape[2 if transposed else 1]
    columns = weights.shape[1 if transposed else 2]
    block = _columns(columns, contracted, GROUPED_BLOCK_ELEMENTS)
    _traced(name, tile)
    w_block = (block, contracted) if transposed else (contracted, block)
    return pl.pallas_call(
        functools.partial(_grouped_rows_kernel, tile=tile,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(columns // block, walk.steps[0]),
            in_specs=[
                pl.BlockSpec((tile, contracted),
                             lambda c, s, b, g, tile_ref, n: (tile_ref[s], 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (tile, block), lambda c, s, b, g, tile_ref, n: (tile_ref[s], c)),
            scratch_shapes=[pltpu.VMEM((2,) + w_block, weights.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((rows, columns), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=GROUPED_VMEM_BYTES),
        interpret=interpret, name="hvd_moe_" + name)(
            *walk, lhs, weights)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_rows(lhs, weights, walk: Walk, *, tile=None, interpret=False):
    """``out[r] = lhs[r] @ weights[e(r)]``: ``lhs`` ``[R, K]`` sorted by
    group as ``walk`` says, ``weights`` ``[held, K, N]`` of ``lhs``'s
    type; float32 accumulation, rounded once to ``lhs``'s type (what
    ``lax.ragged_dot(preferred_element_type=lhs.dtype)`` gives).  Every
    tile a group reaches is written whole, zeros in the rows past the
    last group's end; a tile past them is not written."""
    return _grouped_rows("grouped_rows", lhs, weights, walk, tile, False,
                         interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_rows_t(d_out, weights, walk: Walk, *, tile=None,
                   interpret=False):
    """``d_lhs[r] = d_out[r] @ weights[e(r)]^T``, ``d_out`` ``[R, N]``:
    :func:`grouped_rows`'s transpose in ``lhs``, the same walk
    contracting over the weights' LAST axis, so no transposed copy of
    them is made."""
    return _grouped_rows("grouped_rows_t", d_out, weights, walk, tile, True,
                         interpret)


def _grouped_weights_kernel(bounds_ref, group_ref, tile_ref, steps_ref,
                            lhs_ref, d_ref, o_ref, acc, *, tile):
    step = pl.program_id(1)
    group, tile_of = group_ref[step], tile_ref[step]

    @pl.when(_changes_at(group_ref, step))
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(_some_rows(bounds_ref, group, tile_of, tile))
    def _():
        # Both operands are masked by a select: a row of another group
        # is finite, a row past the last group's end may be anything.
        lhs, d = (lax.convert_element_type(
            lax.select(_rows_of_group(bounds_ref, group, tile_of, ref.shape,
                                      tile), _float(ref),
                       jnp.zeros(ref.shape, jnp.float32)), ref.dtype)
            for ref in (lhs_ref, d_ref))
        acc[...] += lax.dot_general(lhs, d, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    # Past the last step the walk names the last group again.
    @pl.when(lax.bitwise_or(
        step == steps_ref[0] - 1,
        group_ref[lax.min(step + 1, group_ref.shape[0] - 1)] != group))
    def _():
        o_ref[...] = lax.convert_element_type(acc[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_weights(lhs, d_out, walk: Walk, *, tile=None, interpret=False):
    """``d_weights[e] = lhs[rows of e]^T @ d_out[rows of e]`` ``[held,
    K, N]``: :func:`grouped_rows`'s transpose in ``weights``, summed in
    a float32 accumulator in VMEM over the row tiles of a group and
    rounded once to ``lhs``'s type; zeros for a group that holds no
    row."""
    rows, held = lhs.shape[0], walk.bounds.shape[0] - 1
    tile = tile or grouped_tile(rows)
    inner, columns = lhs.shape[1], d_out.shape[1]
    block = _columns(columns, inner, GROUPED_BLOCK_ELEMENTS)
    _traced("grouped_weights", tile)
    return pl.pallas_call(
        functools.partial(_grouped_weights_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(columns // block, walk.steps[0]),
            in_specs=[
                pl.BlockSpec((tile, inner),
                             lambda c, s, b, g, tile_ref, n: (tile_ref[s], 0)),
                pl.BlockSpec((tile, block),
                             lambda c, s, b, g, tile_ref, n: (tile_ref[s], c))],
            out_specs=pl.BlockSpec(
                (None, inner, block),
                lambda c, s, b, group_ref, t, n: (group_ref[s], 0, c)),
            scratch_shapes=[pltpu.VMEM((inner, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((held, inner, columns), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=GROUPED_VMEM_BYTES),
        interpret=interpret, name="hvd_moe_grouped_weights")(
            *walk, lhs, d_out)
