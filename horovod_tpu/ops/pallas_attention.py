"""Flash attention as Pallas TPU kernels, forward and backward.

The hot op of transformer training, written TPU-first.  No array with
two sequence dimensions ever exists in HBM: each kernel holds a block
of queries and a block of keys and values in VMEM (``SEQ_BLOCK`` rows
of each; for sequences up to that length, the whole head) and walks it
in tiles of scores that live and die in VMEM.

* ``hvd_flash_fwd``: online softmax over the tiles of a query block;
  writes the output and each row's log-sum-exp (one float32 a row,
  rows along the lanes).
* ``hvd_flash_bwd``: grid over kv blocks and, last, q blocks, both
  sequential; recomputes ``p = exp(s - lse)`` tile by tile, once, and
  accumulates all three gradients in float32 scratch: dK and dV of the
  kv block over the q blocks, dQ of the block's heads for the WHOLE
  (padded) query sequence over the kv blocks, written when it is whole.
  Five products and one ``exp`` a tile.

Where that dQ does not fit ``FUSED_DQ_BYTES`` of VMEM (a rule on static
shapes: past 21845 rows at two heads of 192, 65536 at 128 lanes) the
backward is the two kernels it was before PR 38, seven products and two
``exp`` a tile: ``hvd_flash_bwd_dkv``, the same body without dQ, and
``hvd_flash_bwd_dq``, grid over q blocks, kv blocks sequential.  Gauges
``hvd_flash_bwd_lowerings{form}`` and ``hvd_flash_bwd_dq_vmem_bytes``
say which a process lowered.

The kernels read and write the models' own ``[B, S, H, D]`` arrays,
seen as ``[B, S, H * D]``: a block holds as many heads as fill the 128
lanes (two of GPT-2's), each head a lane slice of it, so XLA moves
nothing around the kernels and every store is lane-dense.

Two head sizes: queries and keys share one (``D``), values and the
output may have another (``Dv``; latent attention's keys carry a
positional part that its values lack).  A block then holds the same
heads of both widths, as few as make both whole lanes (two of 192 and
128); the forward's accumulator, dV and ``di`` are ``Dv`` wide, dQ and
dK ``D`` wide, and nothing is padded.  Where the two are equal the
kernels are the ones they were.

Tiles wholly above the causal diagonal are skipped, tiles on it are
masked, tiles below it carry no mask arithmetic.  A causal call may name
a ``window``: query ``i`` then sees the keys ``j`` with ``0 <= i - j <
window``, a band under the diagonal.  Tiles wholly left of the band are
skipped too, tiles that straddle its left edge are masked, tiles inside
it carry no mask arithmetic; and the grid's sequential dimension does
not walk the whole sequence but the ``ceil((window - 1) / block) + 1``
blocks the band reaches from the other dimension's block (three at a
window of 2048 over blocks of 1024), placed by the index maps: clamped
at the sequence's end, a clamped step skipped, so that no block the band
cannot reach is fetched.  The window calls are named
``hvd_flash_fwd_window`` and ``hvd_flash_bwd_window``.  Both products of a
tile take their operands in the dtype they arrive in (bf16 in the
models) and accumulate in float32; ``p`` and ``ds`` are cast to the
operand dtype before the second product, as the einsum path casts its
probabilities; softmax statistics, ``di`` and accumulators stay
float32.  Float32 inputs compute in float32 throughout.

Pairs with the mesh-level sequence parallelism in
:mod:`horovod_tpu.parallel.attention`: ring attention rotates K/V
shards between chips while these kernels compute each local block.

The kernels are compiled by Mosaic unless the caller passes
``interpret=True``, which is how the CPU tests run the same bodies.
Each is called through one jitted function, so the unrolled layers of
a model share one lowering.  The forward names its output and its
log-sum-exp ``flash_out`` and ``flash_lse`` (``checkpoint_name``): a
model that recomputes its layers keeps those two by policy, and the
forward kernel runs once.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..common import metrics

NEG_INF = -1e30

# The tile of scores (queries x keys) the kernels compute at a time,
# and the rows of queries and of keys a grid step holds.  Picked on a
# TPU v5 lite at [16, 1024, 16, 64] causal bf16 (PERF.md, PR 25).  A
# grid step costs about a third of a microsecond and one head's causal
# square a microsecond of MXU time: so a step holds a whole head
# (SEQ_BLOCK rows) and the kernel walks it in tiles, deciding
# statically which to skip.  Large tiles feed the MXU better, small
# ones skip more of the square above the diagonal; and every tile is
# unrolled code that every start loads with the step, about a tenth of
# a second a tile (dK/dV alone runs 13 % faster at 256 x 256 and 22 %
# faster at 128 x 128, at 1.5 and 8 s of every warm start).
TILE = (512, 512)
SEQ_BLOCK = 1024
LANES = 128
# The backward pass is one kernel where a float32 dQ of a block's heads
# for the whole (padded) query sequence fits this much of the 128 MiB of
# VMEM: 21845 rows at two heads of 192, 65536 at 128 lanes (a sequence of
# 16384 at 128 lanes takes 8 MiB of it, a window call as a full one: the
# band bounds the blocks a kv block meets, not the rows dQ has).
FUSED_DQ_BYTES = 32 << 20
DEFAULT_VMEM_BYTES = 16 << 20      # Mosaic's scoped limit where none is set

_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def _when(cond, fn):
    """``pl.when`` that decides at trace time where it can."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _block_ids(nq: int, nk: int, q_axis: int):
    """The q and kv block of this grid step (grid: batch, head group,
    then the two); a plain 0 where the grid has one block, so that
    every causal decision of a whole head is made while tracing."""
    i = pl.program_id(q_axis) if nq > 1 else 0
    j = pl.program_id(5 - q_axis) if nk > 1 else 0
    return i, j


def band_steps(window: int, block: int, blocks: int) -> int:
    """Blocks of ``block`` rows that a band of ``window`` keys reaches
    from one block of the other dimension, of the ``blocks`` there are:
    the block on the diagonal and those the ``window - 1`` keys before
    a block's first query lie in."""
    return min(blocks, -(-(window - 1) // block) + 1)


class _Walk:
    """Where a grid step stands.  The grid's last dimension is walked
    in sequence under the other block's accumulators: all its blocks
    (``steps`` None; then this is ``_block_ids`` and the tests the
    kernels made of it), or, in a window call, the ``steps`` blocks the
    band reaches: kv blocks ``i - steps + 1`` to ``i`` under q block
    ``i`` (``q_axis`` 2), q blocks ``j`` to ``j + steps - 1`` over kv
    block ``j`` (``q_axis`` 3).  A step that falls off the sequence is
    not ``valid``: its index map is clamped to a block the walk meets
    anyway, nothing is fetched for it and nothing computed.  ``first``
    and ``last`` are made where they are asked for."""

    def __init__(self, nq: int, nk: int, q_axis: int, steps):
        self.nq, self.nk, self.q_axis, self.steps = nq, nk, q_axis, steps
        if steps is None:
            self.i, self.j = _block_ids(nq, nk, q_axis)
            self.at = self.j if q_axis == 2 else self.i
            self.valid = True
            return
        outer = pl.program_id(2) if max(nq, nk) > 1 else 0
        self.at = pl.program_id(3) if steps > 1 else 0
        if q_axis == 2:
            self.i, self.j = outer, outer - (steps - 1) + self.at
            self.valid = self.j >= 0
        else:
            self.j, self.i = outer, outer + self.at
            self.valid = self.i <= nq - 1

    def first(self):
        return self.at == 0

    def last(self):
        if self.steps is None:
            return self.at == (self.nk if self.q_axis == 2 else self.nq) - 1
        return self.at == self.steps - 1

    def first_of_q(self):
        """At the first kv block q block ``i`` meets (fused backward:
        where its dQ starts at zero)."""
        if self.steps is None:
            return self.j == 0
        return ((self.j == 0) | (self.at == self.steps - 1)) & self.valid

    def last_of_q(self):
        """The last kv block q block ``i`` meets: the one on the
        diagonal in a window call."""
        if self.steps is None:
            return self.j == self.nk - 1
        return self.at == 0


def _tile_state(q0, k0, *, causal, window, sq, sk, skv):
    """``(run, masked)`` of the tile of ``sq`` queries from ``q0`` and
    ``sk`` keys from ``k0``: whether any of its scores counts, and
    whether some do not (it straddles the diagonal, the band's left
    edge or the end of the keys).  Plain booleans where the positions
    are plain numbers."""
    run, masked = True, False
    if causal:
        run = k0 <= q0 + (sq - 1)
        masked = k0 + (sk - 1) > q0
    if window is not None:
        # The last key against the first query, the first key against
        # the last: the band holds the keys less than ``window`` back.
        run = run & (k0 + (sk - 1) > q0 - window)
        masked = masked | (k0 <= q0 + (sq - 1) - window)
    if skv is not None:
        run = run & (k0 < skv)
        masked = masked | (k0 + sk > skv)
    return run, masked


def _both(a, b):
    """``a and b`` of two conditions, each a plain bool or traced."""
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return a & b


def _no(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


SELECTED = "selected"


def _tiles(fn, i, j, *, causal, bq, bk, sq, sk, skv=None,
           keys_outer=False, window=None, valid=True, selected_from=None):
    """Walk block (i, j) of the scores tile by tile: ``fn(masked, rows,
    cols, q0, k0)`` for every tile that is neither wholly above the
    causal diagonal, nor wholly left of the ``window``'s band, nor
    wholly padding; ``masked`` says whether the tile straddles one of
    the three (``skv``: the true number of keys, given where the keys
    are padded at all).  Nothing where the grid step is not ``valid``
    (``_Walk``).  In a call with a selection (``selected_from``: the
    first query that does not keep every causal key) a tile that holds
    such a query is ``masked`` ``SELECTED``: its scores count where the
    selection's bits say, which hold the diagonal and the padding too;
    a tile of earlier queries alone is the causal call's."""
    pairs = [(a, c) for a in range(bq // sq) for c in range(bk // sk)]
    if keys_outer:
        pairs.sort(key=lambda ac: (ac[1], ac[0]))
    for a, c in pairs:
        q0, k0 = i * bq + a * sq, j * bk + c * sk
        tile = functools.partial(
            fn, rows=slice(a * sq, (a + 1) * sq),
            cols=slice(c * sk, (c + 1) * sk), q0=q0, k0=k0)
        run, masked = _tile_state(q0, k0, causal=causal, window=window,
                                  sq=sq, sk=sk, skv=skv)
        if valid is not True:
            run = run & valid
        if selected_from is not None:
            chosen = q0 + (sq - 1) >= selected_from
            plain = _both(run, _no(chosen))
            _when(_both(run, chosen), functools.partial(tile, SELECTED))
            _when(_both(plain, masked), functools.partial(tile, True))
            _when(_both(plain, _no(masked)), functools.partial(tile, False))
        elif isinstance(masked, bool):
            _when(run, functools.partial(tile, masked))
        else:
            _when(run & masked, functools.partial(tile, True))
            _when(run & jnp.logical_not(masked),
                  functools.partial(tile, False))


def _offsets(causal: bool, shape, q_dim: int):
    """``qpos - kpos`` over a tile that starts at query 0 and key 0:
    made once a kernel, so that a tile on the diagonal pays one
    comparison with a scalar for its mask.  ``q_dim`` is the tile's
    query dimension (1 where the scores are transposed)."""
    if not causal:
        return None
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim))


def _mask(s, offsets, q0, k0, q_dim: int, skv=None, window=None):
    """The scores of a masked tile with those that do not count at
    ``NEG_INF``: keys after the query (``offsets`` of a causal kernel),
    keys ``window`` or more before it and, where ``skv`` is given, keys
    past the end of the sequence."""
    keep = None if offsets is None else offsets >= k0 - q0
    if window is not None:
        keep = keep & (offsets < window + k0 - q0)
    if skv is not None:
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_dim)
        keep = (kpos < skv) if keep is None else keep & (kpos < skv)
    return jnp.where(keep, s, NEG_INF)


def _selected_scores(s, sel_ref, rows, cols):
    """The scores ``s`` ``[keys, queries]`` of a tile with those the
    selection leaves out at ``NEG_INF``.  ``sel_ref`` holds the block's
    words of the packed mask (``ops/dsa.py``: ``[1, keys / 32,
    queries]`` int32, a tile of keys ``keys / 32`` rows of it, bit ``b``
    of row ``r`` the tile's key ``b * keys / 32 + r``): 32 shifts of the
    tile's words, each ``keys / 32`` consecutive rows of the scores."""
    from .dsa import unpack_tile
    words = sel_ref[0, cols.start // 32:cols.stop // 32, rows]
    return jnp.where(unpack_tile(words), s, NEG_INF)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _head_lanes(h: int, d: int, dv: int):
    """Head ``h``'s lanes of a block of queries or keys, and of a block
    of values (or of anything as wide as they)."""
    return slice(h * d, (h + 1) * d), slice(h * dv, (h + 1) * dv)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                vt_ref, *, causal, nq, nk, heads, d, dv, bq, bk, sq, sk, skv,
                window, steps, sel_ref=None, selected_from=None):
    """Scores, statistics and accumulator all transposed ([keys,
    queries], [1, queries], [D, queries]): the running max and sum of
    a query then lie along the lanes, a few registers a tile, and
    reducing over keys is elementwise between registers.  With queries
    down the sublanes every statistic costs a register per eight
    rows, more than the tile's own arithmetic at 512 keys."""
    walk = _Walk(nq, nk, 2, steps)
    i, j = walk.i, walk.j

    def init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
    _when(walk.first(), init)

    # The values once a grid step as [heads * Dv, keys], for the
    # transposed accumulator: a head's are then a sublane slice.
    def turn_values():
        vt_ref[:] = v_ref[0].T
    _when(walk.valid, turn_values)

    offsets = _offsets(causal, (sk, sq), 1)
    for h in range(heads):
        lanes, vlanes = _head_lanes(h, d, dv)

        def tile(masked, rows, cols, q0, k0, h=h, lanes=lanes,
                 vlanes=vlanes):
            vt = vt_ref[vlanes, cols]                       # [Dv, sk]
            st = _dot(k_ref[0, cols, lanes], q_ref[0, rows, lanes],
                      _NT)                          # q arrives scaled
            if masked == SELECTED:
                st = _selected_scores(st, sel_ref, rows, cols)
            elif masked:
                st = _mask(st, offsets, q0, k0, 1, skv, window)  # [sk, sq]
            m_prev = m_ref[h, :, rows]                      # [1, sq]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h, :, rows] = l_ref[h, :, rows] * corr + jnp.sum(
                pt, axis=0, keepdims=True)
            acc_ref[h, :, rows] = acc_ref[h, :, rows] * corr + _dot(
                vt, pt.astype(vt.dtype))                    # [Dv, sq]
            m_ref[h, :, rows] = m_new

        _tiles(tile, i, j, causal=causal, bq=bq, bk=bk, sq=sq, sk=sk,
               skv=skv, keys_outer=True, window=window, valid=walk.valid,
               selected_from=selected_from)

    def finalize():
        l = l_ref[:]                                        # [heads, 1, bq]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows
        out_t = (acc_ref[:] / l).reshape(heads * dv, bq)
        o_ref[0] = out_t.T.astype(o_ref.dtype)              # [bq, heads * Dv]
        lse_ref[0] = m_ref[:] + jnp.log(l)
    _when(walk.last(), finalize)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, *rest,
                scale, causal, nq, nk, heads, d, dv, bq, bk, sq, sk, skv,
                window, steps, sel_ref=None, selected_from=None):
    """Scores transposed, as in the forward: ``lse`` and ``di`` come
    as rows and broadcast down the sublanes.  dK and dV are summed over
    the q blocks, the grid's last dimension.

    ``rest`` is their two accumulators and, before them, what the fused
    form adds: dQ's output block, every row of the group's queries.
    After them its accumulator, carried across the kv blocks as well and
    held transposed, ``[q block, heads * D, queries]``, so that with the
    keys turned once a kv block (``kt_ref``, as the forward turns its
    values) ``dQ^T += K^T dS^T`` is a plain product of the transposed
    ``ds`` this kernel has; a q block is turned back and written, times
    ``scale``, when the last kv block has been added.  Keys ascend for
    every query row, the order ``_dq_kernel`` adds in.  In a window
    call a kv block meets the ``steps`` q blocks from its own on, and a
    q block's dQ starts at the first kv block of its band and is whole
    at the block on the diagonal."""
    walk = _Walk(nq, nk, 3, steps)
    i, j = walk.i, walk.j
    fused = len(rest) > 2
    if fused:
        dq_ref, dk_acc, dv_acc, dqt_acc, kt_ref = rest
    else:
        dk_acc, dv_acc = rest

    def init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if fused:
            kt_ref[:] = k_ref[0].T
    _when(walk.first(), init)
    if fused:
        def init_dq():
            dqt_acc[i] = jnp.zeros(dqt_acc.shape[1:], dqt_acc.dtype)
        _when(walk.first_of_q(), init_dq)

    offsets = _offsets(causal, (sk, sq), 1)
    for h in range(heads):
        lanes, vlanes = _head_lanes(h, d, dv)

        def tile(masked, rows, cols, q0, k0, h=h, lanes=lanes,
                 vlanes=vlanes):
            q, do = q_ref[0, rows, lanes], do_ref[0, rows, vlanes]
            st = _dot(k_ref[0, cols, lanes], q, _NT)        # [sk, sq]
            if masked == SELECTED:
                st = _selected_scores(st, sel_ref, rows, cols)
            elif masked:
                st = _mask(st, offsets, q0, k0, 1, skv, window)
            pt = jnp.exp(st - lse_ref[0, h, :, rows])       # rows: [1, sq]
            dv_acc[cols, vlanes] += _dot(pt.astype(do.dtype), do)
            dpt = _dot(v_ref[0, cols, vlanes], do, _NT)
            dst = (pt * (dpt - di_ref[0, h, :, rows])).astype(q.dtype)
            dk_acc[cols, lanes] += _dot(dst, q)             # q scaled
            if fused:
                dqt_acc[i, lanes, rows] += _dot(kt_ref[lanes, cols], dst)

        _tiles(tile, i, j, causal=causal, bq=bq, bk=bk, sq=sq, sk=sk,
               skv=skv, keys_outer=True, window=window, valid=walk.valid,
               selected_from=selected_from)

    def finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
    _when(walk.last(), finalize)
    if fused:
        def write_dq():
            dq_ref[0, pl.ds(i * bq, bq), :] = (dqt_acc[i].T * scale).astype(
                dq_ref.dtype)
        _when(walk.last_of_q(), write_dq)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               *, scale, causal, nq, nk, heads, d, dv, bq, bk, sq, sk, skv,
               window, steps):
    """Scores as [queries, keys], so that dQ = dS K is a plain
    product; the row statistics are turned from lanes to sublanes."""
    walk = _Walk(nq, nk, 2, steps)
    i, j = walk.i, walk.j

    def init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
    _when(walk.first(), init)

    offsets = _offsets(causal, (sq, sk), 0)
    for h in range(heads):
        lanes, vlanes = _head_lanes(h, d, dv)

        def tile(masked, rows, cols, q0, k0, h=h, lanes=lanes,
                 vlanes=vlanes):
            k = k_ref[0, cols, lanes]
            s = _dot(q_ref[0, rows, lanes], k, _NT)         # [sq, sk]
            if masked:
                s = _mask(s, offsets, q0, k0, 0, skv, window)
            p = jnp.exp(s - lse_ref[0, h, 0, rows][:, None])
            dp = _dot(do_ref[0, rows, vlanes], v_ref[0, cols, vlanes], _NT)
            ds = p * (dp - di_ref[0, h, 0, rows][:, None])
            dq_acc[rows, lanes] += _dot(ds.astype(k.dtype), k)

        _tiles(tile, i, j, causal=causal, bq=bq, bk=bk, sq=sq, sk=sk,
               skv=skv, window=window, valid=walk.valid)

    def finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)
    _when(walk.last(), finalize)


def _blocking(seq: int, tile: int, seq_block: int):
    """``(tile, block, padded)``: the tile is the caller's, cut to the
    sequence; a block is as many whole tiles as ``seq_block`` holds;
    the sequence is padded to whole blocks (Pallas would clamp a
    partial block to fit, which mis-positions the tail)."""
    tile = min(tile, seq)
    n_tiles = -(-seq // tile)
    block = tile * min(n_tiles, max(1, seq_block // tile))
    return tile, block, -(-seq // block) * block


def band_tiles(seq: int, window: int, tile=TILE,
               seq_block: int = SEQ_BLOCK) -> dict:
    """What a causal window call does with one head's square of
    ``seq`` queries and keys, from the static shapes and by the
    kernels' own rule (``_tile_state``): tiles ``walked`` (computed),
    of them ``masked`` (they straddle the diagonal, the band's left
    edge or the padding), tiles on or under the diagonal ``skipped``
    because the band does not reach them, and ``fill``, the scores
    inside the band over the scores of the tiles walked."""
    sq, bq, padded = _blocking(seq, tile[0], seq_block)
    sk = _blocking(seq, tile[1], seq_block)[0]
    skv = seq if padded != seq else None
    walked = masked = skipped = 0
    for q0 in range(0, padded, sq):
        for k0 in range(0, padded, sk):
            run, mask = _tile_state(q0, k0, causal=True, window=window,
                                    sq=sq, sk=sk, skv=skv)
            causal_run = _tile_state(q0, k0, causal=True, window=None,
                                     sq=sq, sk=sk, skv=skv)[0]
            walked += run
            masked += run and mask
            skipped += causal_run and not run
    inside = min(window, seq)
    scores = inside * (inside + 1) // 2 + (seq - inside) * inside
    return {"walked": walked, "masked": masked, "skipped": skipped,
            "fill": scores / (walked * sq * sk)}


def _heads_per_block(heads: int, d: int, dv: int) -> int:
    """As few heads as make a block's last dimension whole lanes, at
    the queries' and keys' width and at the values'; all of them where
    no such count divides ``heads`` (a block as wide as the array is
    always allowed)."""
    for g in range(1, heads):
        if heads % g == 0 and (g * d) % LANES == 0 \
                and (g * dv) % LANES == 0:
            return g
    return heads


def _pad_seq(x, rows: int):
    pad = rows - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


class _Plan:
    """Blocks, grid and index maps of one kernel call on ``[B, S, H *
    D]`` queries and keys and ``[B, S, H * Dv]`` values.  The grid is
    (batch, head groups, x, y), y sequential: x the q blocks and y the
    kv blocks (``q_axis`` 2), or the other way round (``q_axis`` 3).
    With a ``window`` y is the band's ``steps`` blocks (``_Walk``), and
    the index maps of what y walks place them from x's block."""

    def __init__(self, q, k, v, heads, tile, seq_block, q_axis, window=None):
        self.batch, self.sq_len, width = q.shape
        self.skv_len = k.shape[1]
        self.d, self.dv = width // heads, v.shape[2] // heads
        self.g = _heads_per_block(heads, self.d, self.dv)
        self.sq, self.bq, self.sq_pad = _blocking(self.sq_len, tile[0],
                                                  seq_block)
        self.sk, self.bk, self.skv_pad = _blocking(self.skv_len, tile[1],
                                                   seq_block)
        self.nq = self.sq_pad // self.bq
        self.nk = self.skv_pad // self.bk
        blocks = (self.nq, self.nk) if q_axis == 2 else (self.nk, self.nq)
        self.steps = None
        if window is not None:
            if (self.sq_len, self.bq) != (self.skv_len, self.bk):
                raise ValueError(
                    "a window is a band of one sequence's own keys: as "
                    "many keys as queries, in blocks of one size (got "
                    "%d queries in blocks of %d, %d keys in blocks of %d)"
                    % (self.sq_len, self.bq, self.skv_len, self.bk))
            self.steps = band_steps(window, self.bk, self.nk)
            blocks = (blocks[0], self.steps)
        self.grid = (self.batch, heads // self.g) + blocks
        self.q_at, self.k_at = q_axis, 5 - q_axis
        # What every kernel is told; ``skv`` only where keys are
        # padded, for the kernels to hide them: a padded key scores 0,
        # not nothing.
        self.sizes = dict(nq=self.nq, nk=self.nk, heads=self.g, d=self.d,
                          dv=self.dv, bq=self.bq, bk=self.bk, sq=self.sq,
                          sk=self.sk,
                          skv=(self.skv_len if self.skv_pad != self.skv_len
                               else None),
                          window=window, steps=self.steps)

    def _block(self, at):
        """``ids -> `` the block of the grid's dimension ``at`` (2 or
        3) that a step reads: its own index, or in a window call, for
        the dimension the band is walked in, the block ``_Walk`` names,
        clamped into the sequence."""
        if self.steps is None or at == 2:
            return lambda ids: ids[at]
        if self.q_at == 2:      # kv blocks up to q block ids[2]'s own
            return lambda ids: jax.lax.max(
                ids[2] - (self.steps - 1) + ids[3], 0)
        return lambda ids: jax.lax.min(ids[2] + ids[3], self.nq - 1)

    def _rows(self, block, at, d):
        of = self._block(at)
        return pl.BlockSpec((1, block, self.g * d),
                            lambda *ids: (ids[0], of(ids), ids[1]))

    def q_rows(self):
        """A block of queries (or of dQ)."""
        return self._rows(self.bq, self.q_at, self.d)

    def o_rows(self):
        """A block of the output (or of its cotangent): the queries'
        rows at the values' width."""
        return self._rows(self.bq, self.q_at, self.dv)

    def k_rows(self):
        return self._rows(self.bk, self.k_at, self.d)

    def v_rows(self):
        return self._rows(self.bk, self.k_at, self.dv)

    def q_stats(self):
        """Row statistics, [B, H, 1, Sq]: a row of floats a head."""
        of = self._block(self.q_at)
        return pl.BlockSpec((1, self.g, 1, self.bq),
                            lambda *ids: (ids[0], ids[1], 0, of(ids)))

    def sel_words(self):
        """The words of a packed selection (``ops/dsa.py``) that a q
        block and a kv block share: ``[B, keys / 32, queries]``."""
        q_of, k_of = self._block(self.q_at), self._block(self.k_at)
        return pl.BlockSpec((1, self.bk // 32, self.bq),
                            lambda *ids: (ids[0], k_of(ids), q_of(ids)))

    def q_whole(self):
        """Every (padded) row of the group's queries, whichever block
        the grid is at: the fused backward's dQ, written back once."""
        return pl.BlockSpec((1, self.sq_pad, self.g * self.d),
                            lambda *ids: (ids[0], 0, ids[1]))

    def dq_bytes(self):
        """A float32 accumulator of dQ for one group's whole sequence."""
        return self.sq_pad * self.g * self.d * 4

    def call(self, kernel, name, in_specs, out_specs, out_shape, scratch,
             interpret, carried="parallel", vmem_limit=None):
        """``carried``: what the grid's third dimension is to Mosaic,
        ``"arbitrary"`` where an accumulator is carried across it."""
        return pl.pallas_call(
            kernel, grid=self.grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", carried,
                                     "arbitrary"),
                vmem_limit_bytes=vmem_limit),
            interpret=interpret, name=name)


def _named(kernel: str, window, selected=None) -> str:
    """A window call's kernels, and those of a call with a selection,
    under names of their own, so that a trace tells a band's walk and a
    masked triangle's from a triangle's."""
    if selected is not None:
        return kernel + "_selected"
    return kernel if window is None else kernel + "_window"


def _with_selection(kernel, inputs: int, p: "_Plan", selected, topk):
    """``kernel`` taking the block's words of ``selected`` (the packed
    mask, padded to ``p``'s blocks) as one more input after the
    ``inputs`` it has, or as it is where ``selected`` is None:
    ``(kernel, operands, specs)``, the last two to be appended to the
    call's."""
    if selected is None:
        return kernel, (), []
    if p.sk % 32 or (p.sq_len, p.bq) != (p.skv_len, p.bk):
        raise ValueError(
            "a selection is of one sequence's own keys, in tiles of whole "
            "words: as many keys as queries in blocks of one size (got %d "
            "queries in blocks of %d, %d keys in blocks of %d, tiles of %d)"
            % (p.sq_len, p.bq, p.skv_len, p.bk, p.sk))
    words = jnp.pad(selected, (
        (0, 0), (0, p.skv_pad // 32 - selected.shape[1]),
        (0, p.sq_pad - selected.shape[2])))

    def taking(*refs, **sizes):
        return kernel(*refs[:inputs], *refs[inputs + 1:],
                      sel_ref=refs[inputs], selected_from=topk, **sizes)
    return taking, (words,), [p.sel_words()]


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "tile", "seq_block", "interpret", "window", "topk"))
def _fwd_call(q, k, v, selected=None, *, heads, causal, tile, seq_block,
              interpret, window=None, topk=None):
    """``q`` (scaled), ``k``: [B, S, H * D]; ``v``: [B, S, H * Dv].
    Returns the output [B, Sq, H * Dv] and the log-sum-exp of every
    (padded) row, [B, H, 1, Sq padded].  ``selected``: a packed
    selection of keys a query (``ops/dsa.py``), ``topk`` the first query
    that does not keep every causal key."""
    p = _Plan(q, k, v, heads, tile, seq_block, 2, window)
    kernel, words, words_spec = _with_selection(_fwd_kernel, 3, p, selected,
                                                topk)
    out, lse = p.call(
        functools.partial(kernel, causal=causal, **p.sizes),
        _named("hvd_flash_fwd", window, selected),
        [p.q_rows(), p.k_rows(), p.v_rows()] + words_spec,
        [p.o_rows(), p.q_stats()],
        [jax.ShapeDtypeStruct((p.batch, p.sq_pad, heads * p.dv), q.dtype),
         jax.ShapeDtypeStruct((p.batch, heads, 1, p.sq_pad), jnp.float32)],
        [pltpu.VMEM((p.g, 1, p.bq), jnp.float32),
         pltpu.VMEM((p.g, 1, p.bq), jnp.float32),
         pltpu.VMEM((p.g, p.dv, p.bq), jnp.float32),
         pltpu.VMEM((p.g * p.dv, p.bk), v.dtype)],
        interpret,
    )(_pad_seq(q, p.sq_pad), _pad_seq(k, p.skv_pad), _pad_seq(v, p.skv_pad),
      *words)
    return out[:, :p.sq_len], lse


def _bwd_operands(p: _Plan, q, k, v, lse, do, di):
    """What both backward kernels read, padded to ``p``'s blocks, and
    the specs to read it by.  Padded query rows have do = 0 and di = 0,
    so they add nothing; ``lse`` comes padded to the forward's blocks
    and is cut to the sequence first."""
    stats = [jnp.pad(x[..., :p.sq_len],
                     ((0, 0),) * 3 + ((0, p.sq_pad - p.sq_len),))
             for x in (lse, di)]
    args = (_pad_seq(q, p.sq_pad), _pad_seq(k, p.skv_pad),
            _pad_seq(v, p.skv_pad), _pad_seq(do, p.sq_pad), *stats)
    specs = [p.q_rows(), p.k_rows(), p.v_rows(), p.o_rows(),
             p.q_stats(), p.q_stats()]
    return args, specs


_LOWERINGS = metrics.gauge(
    "hvd_flash_bwd_lowerings",
    "Lowerings of the flash kernels' backward pass this process made, by "
    "form: fused (one kernel) or split (dK/dV and dQ apart); one a "
    "distinct shape and trace context, as inside and outside a shard_map "
    "(set when the backward is traced)")
_DQ_VMEM_BYTES = metrics.gauge(
    "hvd_flash_bwd_dq_vmem_bytes",
    "Bytes of the float32 dQ accumulator the last fused backward lowering "
    "holds in VMEM (set when the backward is traced)")


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "tile", "seq_block", "interpret",
    "dq_budget", "window", "topk"))
def _bwd_call(q, k, v, lse, do, di, selected=None, *, heads, scale, causal,
              tile, seq_block, interpret, dq_budget, window=None, topk=None):
    """dQ, dK, dV of ``_fwd_call`` (``q`` scaled; dQ is for the
    unscaled one).  ``di`` is ``sum(o * do)`` a row, [B, H, 1, Sq].
    One kernel where dQ's accumulator fits ``dq_budget`` bytes, the
    dK/dV kernel and then the dQ kernel where it does not."""
    p = _Plan(q, k, v, heads, tile, seq_block, 3, window)
    fused = p.dq_bytes() <= dq_budget
    _LOWERINGS.inc(1, form="fused" if fused else "split")
    _LOWERINGS.inc(0, form="split" if fused else "fused")   # reads 0, not absent
    args, specs = _bwd_operands(p, q, k, v, lse, do, di)
    kernel, words, words_spec = _with_selection(_bwd_kernel, len(args), p,
                                                selected, topk)
    if selected is not None and not fused:
        raise ValueError(
            "a call with a selection has the fused backward alone: dQ of %d "
            "rows takes %d bytes of VMEM, over %d"
            % (p.sq_pad, p.dq_bytes(), dq_budget))
    out_specs = [p.k_rows(), p.v_rows()]
    out_shape = [jax.ShapeDtypeStruct(args[1].shape, k.dtype),
                 jax.ShapeDtypeStruct(args[2].shape, v.dtype)]
    scratch = [pltpu.VMEM((p.bk, p.g * p.d), jnp.float32),
               pltpu.VMEM((p.bk, p.g * p.dv), jnp.float32)]
    vmem_limit = None
    if fused:
        _DQ_VMEM_BYTES.set(p.dq_bytes())
        out_specs.append(p.q_whole())
        out_shape.append(jax.ShapeDtypeStruct(args[0].shape, q.dtype))
        scratch += [pltpu.VMEM((p.nq, p.g * p.d, p.bq), jnp.float32),
                    pltpu.VMEM((p.g * p.d, p.bk), k.dtype)]
        # The accumulator, dQ's block twice (Pallas double-buffers an
        # output) and what the scoped default allows for the rest.
        vmem_limit = (p.dq_bytes() + 2 * p.sq_pad * p.g * p.d
                      * q.dtype.itemsize + DEFAULT_VMEM_BYTES)
    dk, dv, *dq = p.call(
        functools.partial(kernel, causal=causal, scale=scale, **p.sizes),
        _named("hvd_flash_bwd" if fused else "hvd_flash_bwd_dkv", window,
               selected),
        specs + words_spec, out_specs, out_shape, scratch, interpret,
        carried="arbitrary" if fused else "parallel",
        vmem_limit=vmem_limit)(*args, *words)
    if fused:
        dq, = dq
    else:
        p = _Plan(q, k, v, heads, tile, seq_block, 2, window)
        args, specs = _bwd_operands(p, q, k, v, lse, do, di)
        dq = p.call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              **p.sizes),
            _named("hvd_flash_bwd_dq", window), specs, p.q_rows(),
            jax.ShapeDtypeStruct(args[0].shape, q.dtype),
            [pltpu.VMEM((p.bq, p.g * p.d), jnp.float32)], interpret)(*args)
    return dq[:, :p.sq_len], dk[:, :p.skv_len], dv[:, :p.skv_len]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, tile, seq_block, interpret, window=None):
    """``flash_attention`` with every choice spelt out; ``seq_block``
    is here for the tests, which cannot hold ``SEQ_BLOCK`` rows."""
    return _flash_vjp_fwd(q, k, v, scale, causal, tile, seq_block,
                          interpret, window)[0]


def _flash_vjp_fwd(q, k, v, scale, causal, tile, seq_block, interpret,
                   window):
    B, Sq, H, D = q.shape
    Dv = v.shape[-1]
    # [B, S, H, D] is [B, S, H * D] for free.  The scale rides on q,
    # in q's dtype (exact for a head size that is a power of four):
    # the kernels then spend nothing on it per score, and dK = dS^T
    # (scale q) comes out scaled by itself.
    q, k, v = ((q * scale).reshape(B, Sq, H * D),
               k.reshape(B, -1, H * D), v.reshape(B, -1, H * Dv))
    out, lse = _fwd_call(q, k, v, heads=H, causal=causal, tile=tile,
                         seq_block=seq_block, interpret=interpret,
                         window=window)
    # Named for a caller's checkpoint policy: a model that recomputes
    # its layers in the backward pass can keep these two and spare the
    # forward kernel's second run.
    out = checkpoint_name(out.reshape(B, Sq, H, Dv), "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, tile, seq_block, interpret, window, res,
                   do):
    q, k, v, out, lse = res
    B, Sq, H, Dv = do.shape
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 1)[:, :, None, :]               # [B, H, 1, Sq]
    grads = _bwd_call(q, k, v, lse, do.reshape(B, Sq, H * Dv), di, heads=H,
                      scale=scale, causal=causal, tile=tile,
                      seq_block=seq_block, interpret=interpret,
                      dq_budget=FUSED_DQ_BYTES, window=window)
    return tuple(g.reshape(B, g.shape[1], H, -1) for g in grads)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_selected(q, k, v, selected, scale, tile, seq_block, interpret,
                    topk):
    return _flash_selected_fwd(q, k, v, selected, scale, tile, seq_block,
                               interpret, topk)[0]


def _flash_selected_fwd(q, k, v, selected, scale, tile, seq_block, interpret,
                        topk):
    B, Sq, H, D = q.shape
    q, k, v = ((q * scale).reshape(B, Sq, H * D),
               k.reshape(B, -1, H * D), v.reshape(B, -1, H * D))
    out, lse = _fwd_call(q, k, v, selected, heads=H, causal=True, tile=tile,
                         seq_block=seq_block, interpret=interpret, topk=topk)
    out = checkpoint_name(out.reshape(B, Sq, H, D), "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse[:, :, 0, :Sq]), (q, k, v, selected, out, lse)


def _flash_selected_bwd(scale, tile, seq_block, interpret, topk, res, cts):
    q, k, v, selected, out, lse = res
    do, _ = cts     # the statistics are handed on detached
    B, Sq, H, D = do.shape
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 1)[:, :, None, :]
    grads = _bwd_call(q, k, v, lse, do.reshape(B, Sq, H * D), di, selected,
                      heads=H, scale=scale, causal=True, tile=tile,
                      seq_block=seq_block, interpret=interpret,
                      dq_budget=FUSED_DQ_BYTES, topk=topk)
    return tuple(g.reshape(B, g.shape[1], H, -1) for g in grads) + (None,)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def selected_tiles(seq: int, topk: int, tile=TILE,
                   seq_block: int = SEQ_BLOCK) -> dict:
    """What a call with a selection does with one head's square of
    ``seq`` queries and keys, from the static shapes and by the
    kernels' own rule (``_tiles``): tiles ``walked`` (every tile on or
    under the diagonal: the selected keys lie scattered over all of
    them), of them ``masked`` (on the diagonal, or holding a query from
    ``topk`` on, whose scores count by the selection's bits), and
    ``skipped`` for holding no selected pair, which no occupancy table
    says yet: 0."""
    sq, _, padded = _blocking(seq, tile[0], seq_block)
    sk = _blocking(seq, tile[1], seq_block)[0]
    walked = masked = 0
    for q0 in range(0, padded, sq):
        for k0 in range(0, padded, sk):
            run, mask = _tile_state(q0, k0, causal=True, window=None,
                                    sq=sq, sk=sk, skv=None)
            walked += run
            masked += run and (mask or q0 + sq - 1 >= topk)
    return {"walked": walked, "masked": masked, "skipped": 0}


def flash_attention_selected(q: jax.Array, k: jax.Array, v: jax.Array,
                             selected: jax.Array, topk: int,
                             scale: Optional[float] = None,
                             interpret: bool = False):
    """Causal flash attention over the keys a SELECTION keeps for each
    query, one selection for every head: ``selected`` is the packed mask
    of ``ops/dsa.py`` (``[B, S / 32, S]`` int32, a subset of the causal
    pairs, in tiles of the kernels' ``TILE`` of keys, or of the whole
    sequence where it is shorter), ``topk`` the first query that does
    not keep every causal key (tiles of earlier queries carry the
    causal call's arithmetic and never read the words).  Returns the output ``[B, S, H, D]``,
    differentiable in ``q``, ``k`` and ``v``, and each row's log-sum-exp
    over its keys ``[B, H, S]`` float32, which is not.  The kernels are
    ``hvd_flash_fwd_selected`` and ``hvd_flash_bwd_selected``: every
    tile on or under the diagonal is walked."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    tile = (TILE[0], min(TILE[1], q.shape[1]))
    return _flash_selected(q, k, v, selected, float(scale), tile, SEQ_BLOCK,
                           bool(interpret), int(topk))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention on ``[B, S, H, D]`` queries and keys and ``[B,
    S, H, Dv]`` values, differentiable; the output is ``Dv`` wide, and
    ``scale`` defaults to ``1 / sqrt(D)``.  ``window`` (causal calls
    only, as many keys as queries): query ``i`` sees the keys ``j`` with
    ``0 <= i - j < window``, its own position counted, and the kernels
    walk that band and fetch nothing left of it.

    ``block_q`` x ``block_k`` is the tile of scores the kernels compute
    at a time (by default ``TILE``, picked on the chip); a grid step
    holds ``SEQ_BLOCK`` rows of queries and of keys in VMEM.  The
    kernels are compiled for the TPU; off the TPU that fails loudly.
    ``interpret=True`` runs the kernel bodies in the Pallas interpreter
    (the CPU tests ask for it by name).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    tile = (int(block_q or TILE[0]), int(block_k or TILE[1]))
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window of %r keys needs a causal call and "
                             "at least the query's own position" % (window,))
        window = int(window)
    return _flash(q, k, v, float(scale), bool(causal), tile, SEQ_BLOCK,
                  bool(interpret), window)
