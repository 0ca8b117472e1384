"""The sparse-attention indexer's two Pallas kernels (``ops/dsa.py`` has
the operators, their XLA forms and the packed mask's layout):
``hvd_dsa_select``, a block of queries' index scores made, thresholded
and packed in VMEM, and ``hvd_dsa_indexer_loss``, the alignment loss and
the indexer's three gradients a tile of pairs at a time."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa import (BITS, INT_MIN, LOSS_QUERIES, SELECT_QUERIES, unpack_tile)

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _sortable(scores):
    """Float32 as int32 in the same order (the sign's bit flips the
    rest): a threshold is then found a bit at a time."""
    # ``+ 0.0``: a negative zero is a zero to ``top_k`` and must map
    # where zero maps.
    bits = pltpu.bitcast(scores + 0.0, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _select_kernel(q_ref, kt_ref, w_ref, sel_ref, lse_ref, key_ref, *,
                   topk, heads, dim, chunk, seq, rows):
    """One block of ``SELECT_QUERIES`` queries (the lanes) against the
    keys up to its last (``chunk`` of them at a time down the
    sublanes).  ``key_ref`` ``[S, queries]`` int32 holds the scores in
    sortable form; keys after a query hold ``INT_MIN``, which no score
    maps to.  The ``topk``-th largest of a query's column is the
    largest ``T`` with ``count(key >= T) >= topk``, found from the top
    bit down in 32 counts; where more keys than ``topk`` lie at or over
    it, those AT it are taken from the lowest position up, the last of
    them found from its top bit down in ``log2(S)`` counts more."""
    block = pl.program_id(1)
    queries = q_ref.shape[1]
    t0 = block * queries
    chunks = (t0 + queries - 1) // chunk + 1          # up to the diagonal
    t_at = t0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, queries), 1)
    s_in = jax.lax.broadcasted_iota(jnp.int32, (chunk, queries), 0)

    def scores_of(c):
        k_t = kt_ref[0, pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
        total = jnp.zeros((chunk, queries), jnp.float32)
        for j in range(heads):
            dots = jax.lax.dot_general(
                k_t, q_ref[0, :, j * dim:(j + 1) * dim],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [chunk, queries]
            total = total + jnp.maximum(dots, 0.0) * w_ref[0, j:j + 1, :]
        return total

    def fill(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        causal = s_in + c * chunk <= t_at
        key_ref[at, :] = jnp.where(causal, _sortable(scores_of(c)), INT_MIN)
        return carry
    jax.lax.fori_loop(0, chunks, fill, 0)

    def count(test):
        """``[1, queries]`` int32: keys of each column that ``test``
        (of a chunk's keys and their positions) holds for."""
        def add(c, total):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            hit = test(key_ref[at, :], s_in + c * chunk)
            return total + jnp.sum(hit.astype(jnp.int32), axis=0,
                                   keepdims=True)
        return jax.lax.fori_loop(0, chunks, add,
                                 jnp.zeros((1, queries), jnp.int32))

    # The threshold, the sign first: int32 compares as signed.
    enough = count(lambda key, _: key >= 0) >= topk
    low = jnp.where(enough, 0, INT_MIN)
    for bit in range(30, -1, -1):
        trial = low | (1 << bit)
        enough = count(lambda key, _, trial=trial: key >= trial) >= topk
        low = jnp.where(enough, trial, low)
    # A query with no more than ``topk`` causal keys keeps them all.
    threshold = jnp.maximum(low, INT_MIN + 1)
    over = count(lambda key, _: key > threshold)
    at_it = count(lambda key, _: key == threshold)
    # Of those AT the threshold, the first ``need`` by position: the
    # least ``last`` with ``count(at it, position <= last) >= need``.
    need = jnp.minimum(topk - over, at_it)
    tied = jnp.max(over + at_it - topk) > 0

    def last_taken():
        high = jnp.full((1, queries), 0, jnp.int32)
        # ``high``: the largest position P with count(<= P - 1) < need,
        # built from the top bit down; positions are under ``seq``.
        for bit in range(max(1, (seq - 1).bit_length()) - 1, -1, -1):
            trial = high | (1 << bit)
            below = count(lambda key, pos, trial=trial:
                          (key == threshold) & (pos < trial))
            high = jnp.where(below < need, trial, high)
        return high
    last = jax.lax.cond(tied, last_taken,
                        lambda: jnp.full((1, queries), seq, jnp.int32))

    def taken(key, pos):
        return (key > threshold) | ((key == threshold) & (pos <= last)
                                    & (need > 0))

    # The selected scores' log-sum-exp: the maximum, then the sum.
    def unsortable(key):
        return pltpu.bitcast(key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)

    def chunk_max(c, m):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        key = key_ref[at, :]
        scores = jnp.where(taken(key, s_in + c * chunk), unsortable(key),
                           -jnp.inf)
        return jnp.maximum(m, jnp.max(scores, axis=0, keepdims=True))
    top = jax.lax.fori_loop(0, chunks, chunk_max,
                            jnp.full((1, queries), -jnp.inf, jnp.float32))

    def chunk_sum_and_pack(c, total):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        key = key_ref[at, :]
        keep = taken(key, s_in + c * chunk)
        total = total + jnp.sum(
            jnp.where(keep, jnp.exp(unsortable(key) - top), 0.0), axis=0,
            keepdims=True)
        kept = keep.astype(jnp.int32)
        words = jnp.zeros((rows, queries), jnp.int32)
        for bit in range(BITS):
            words = words | (kept[bit * rows:(bit + 1) * rows, :] << bit)
        sel_ref[0, pl.ds(pl.multiple_of(c * rows, rows), rows), :] = words
        return total
    sel_ref[0] = jnp.zeros(sel_ref.shape[1:], jnp.int32)
    total = jax.lax.fori_loop(0, chunks, chunk_sum_and_pack,
                              jnp.zeros((1, queries), jnp.float32))
    lse_ref[0] = top + jnp.log(total)


@functools.partial(jax.jit, static_argnames=("topk", "key_tile", "interpret"))
def select_call(q_i, k_i, w, *, topk, key_tile, interpret):
    """The selection of ``q_i`` ``[B, S, J, D]``, ``k_i`` ``[B, S, D]``
    and ``w`` ``[B, S, J]`` float32 as one kernel: grid (batch, blocks
    of queries); a step holds its queries, every key, and its column of
    the packed mask."""
    batch, seq, heads, dim = q_i.shape
    queries = min(SELECT_QUERIES, seq)
    if seq % queries or seq % key_tile:
        raise ValueError("the selection's kernel takes whole blocks of %d "
                         "queries and tiles of %d keys: %d positions are not"
                         % (queries, key_tile, seq))
    rows = key_tile // BITS
    kernel = functools.partial(
        _select_kernel, topk=topk, heads=heads, dim=dim, chunk=key_tile,
        seq=seq, rows=rows)
    packed, lse = pl.pallas_call(
        kernel, grid=(batch, seq // queries),
        in_specs=[
            pl.BlockSpec((1, queries, heads * dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, heads, queries), lambda b, i: (b, 0, i))],
        out_specs=[
            pl.BlockSpec((1, seq // BITS, queries), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, queries), lambda b, i: (b, 0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq // BITS, seq), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((seq, queries), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=(seq * queries * 4 + 2 * seq * dim * 2
                              + 2 * seq // BITS * queries * 4 + (24 << 20))),
        interpret=interpret, name="hvd_dsa_select",
    )(q_i.reshape(batch, seq, heads * dim), k_i, w.transpose(0, 2, 1))
    return packed, lse[:, 0]


def _loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, lsei_ref,
                 sel_ref, kl_ref, dq_ref, dw_ref, dk_ref, kl_acc, dq_acc,
                 dw_acc, *, heads, kv_heads, dim, index_heads, index_dim,
                 tile, queries):
    """One block of queries (the lanes) against one tile of keys (the
    sublanes), the grid's last dimension walking the tiles up to the
    block's diagonal.  The main attention's probabilities are made a
    head at a time and summed in VMEM; the index scores twice, once for
    the loss and ``dI`` and once a head for the three gradients.  The
    queries' gradients, ``w``'s and the rows' loss are carried across
    the tiles; a tile's gradient to the keys is written as this block
    of queries' PART of it."""
    block, c = pl.program_id(1), pl.program_id(2)
    last = ((block + 1) * queries - 1) // tile

    @pl.when(c == 0)
    def _():
        kl_acc[:] = jnp.zeros_like(kl_acc)
        dq_acc[:] = jnp.zeros_like(dq_acc)
        dw_acc[:] = jnp.zeros_like(dw_acc)

    @pl.when(c <= last)
    def _():
        keep = unpack_tile(sel_ref[0])                      # [tile, q]
        group = heads // kv_heads
        total = jnp.zeros((tile, queries), jnp.float32)
        for h in range(heads):
            kv = h // group
            st = jax.lax.dot_general(
                k_ref[0, :, kv * dim:(kv + 1) * dim],
                q_ref[0, :, h * dim:(h + 1) * dim], _NT,
                preferred_element_type=jnp.float32)
            total = total + jnp.exp(st - lse_ref[0, h:h + 1, :])
        pbar = jnp.where(keep, total * (1.0 / heads), 0.0)

        k_i = ki_ref[0]                                     # [tile, Di]

        def dots_of(j):
            return jax.lax.dot_general(
                k_i, qi_ref[0, :, j * index_dim:(j + 1) * index_dim], _NT,
                preferred_element_type=jnp.float32)         # [tile, q]
        scores = jnp.zeros((tile, queries), jnp.float32)
        for j in range(index_heads):
            scores = scores + jnp.maximum(dots_of(j), 0.0) * w_ref[0, j:j + 1,
                                                                   :]
        log_q = scores - lsei_ref[0]
        some = pbar > 0.0
        kl_acc[:] += jnp.sum(jnp.where(
            some, pbar * (jnp.log(jnp.where(some, pbar, 1.0)) - log_q), 0.0),
            axis=0, keepdims=True)
        d_scores = jnp.where(keep, jnp.exp(log_q) - pbar, 0.0)
        d_k = jnp.zeros((tile, index_dim), jnp.float32)
        for j in range(index_heads):
            dots = dots_of(j)
            lanes = slice(j * index_dim, (j + 1) * index_dim)
            dw_acc[j:j + 1, :] += jnp.sum(
                d_scores * jnp.maximum(dots, 0.0), axis=0, keepdims=True)
            g = jnp.where(dots > 0.0, d_scores * w_ref[0, j:j + 1, :],
                          0.0).astype(k_i.dtype)
            dq_acc[lanes, :] += jax.lax.dot_general(
                k_i, g, _TN, preferred_element_type=jnp.float32)
            d_k = d_k + jax.lax.dot_general(
                g, qi_ref[0, :, lanes], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_ref[0, 0] = d_k

    @pl.when(c == last)
    def _():
        kl_ref[0] = kl_acc[:]
        dq_ref[0] = dq_acc[:].T.astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[:]


@functools.partial(jax.jit, static_argnames=("scale", "key_tile",
                                             "interpret"))
def loss_call(q_i, k_i, w, q, k, lse, packed, lse_i, *, scale, key_tile,
               interpret):
    """The alignment loss's sum over ``[B, S]`` and its gradients to
    ``q_i``, ``k_i`` and ``w`` as one kernel: grid (batch, blocks of
    queries, tiles of keys), the tiles past a block's diagonal neither
    fetched nor computed."""
    batch, seq, index_heads, index_dim = q_i.shape
    heads, dim = q.shape[2:]
    kv_heads = k.shape[2]
    queries = min(LOSS_QUERIES, seq)
    if seq % queries or seq % key_tile:
        raise ValueError("the alignment loss's kernel takes whole blocks of "
                         "%d queries and tiles of %d keys: %d positions are "
                         "not" % (queries, key_tile, seq))
    blocks, tiles = seq // queries, seq // key_tile
    rows = key_tile // BITS
    up_to = lambda i, c: jnp.minimum(c, ((i + 1) * queries - 1) // key_tile)
    of_q = lambda width: pl.BlockSpec((1, queries, width),
                                      lambda b, i, c: (b, i, 0))
    of_k = lambda width: pl.BlockSpec((1, key_tile, width),
                                      lambda b, i, c: (b, up_to(i, c), 0))
    stat = lambda count: pl.BlockSpec((1, count, queries),
                                      lambda b, i, c: (b, 0, i))
    kernel = functools.partial(
        _loss_kernel, heads=heads, kv_heads=kv_heads, dim=dim,
        index_heads=index_heads, index_dim=index_dim, tile=key_tile,
        queries=queries)
    kl, d_q, d_w, d_k = pl.pallas_call(
        kernel, grid=(batch, blocks, tiles),
        in_specs=[of_q(heads * dim), of_k(kv_heads * dim), stat(heads),
                  of_q(index_heads * index_dim), of_k(index_dim),
                  stat(index_heads), stat(1),
                  pl.BlockSpec((1, rows, queries),
                               lambda b, i, c: (b, up_to(i, c), i))],
        out_specs=[stat(1), of_q(index_heads * index_dim), stat(index_heads),
                   pl.BlockSpec((1, 1, key_tile, index_dim),
                                lambda b, i, c: (b, i, up_to(i, c), 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, index_heads * index_dim),
                                 q_i.dtype),
            jax.ShapeDtypeStruct((batch, index_heads, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, blocks, seq, index_dim),
                                 jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((1, queries), jnp.float32),
            pltpu.VMEM((index_heads * index_dim, queries), jnp.float32),
            pltpu.VMEM((index_heads, queries), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="hvd_dsa_indexer_loss",
    )((q * scale).astype(q.dtype).reshape(batch, seq, heads * dim),
      k.reshape(batch, seq, kv_heads * dim), lse,
      q_i.reshape(batch, seq, index_heads * index_dim), k_i,
      w.transpose(0, 2, 1), lse_i[:, None, :], packed)
    # A block of queries wrote its part of the keys' gradient for the
    # tiles up to its diagonal; what lies past them was never written.
    reached = (jnp.arange(seq)[None, :] // key_tile
               <= ((jnp.arange(blocks)[:, None] + 1) * queries - 1)
               // key_tile)
    d_k = jnp.where(reached[None, :, :, None], d_k, 0.0).sum(1)
    return (kl.sum(), d_q.reshape(q_i.shape), d_k.astype(k_i.dtype),
            d_w.transpose(0, 2, 1))
