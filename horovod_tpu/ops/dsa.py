"""DeepSeek-Sparse-Attention's indexer: which keys a query keeps, and
the loss that teaches the choice.

For one sequence, ``q_i`` ``[S, J, D]`` (``J`` small index heads),
``k_i`` ``[S, D]`` (ONE key head) and ``w`` ``[S, J]``:

    I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])        s <= t

* :func:`select`: ``S_t``, the keys of the ``topk`` largest ``I[t, s]``,
  ``s <= t`` (every causal key where there are no more than ``topk``;
  ties as ``jax.lax.top_k`` breaks them, the lower position first), as a
  PACKED mask, one bit a (query, key) pair, and the log-sum-exp of
  ``I[t, S_t]``.  The scores never reach HBM where the kernel runs.
* :func:`indexer_loss`: ``mean_t KL(pbar[t, S_t] || softmax(I[t,
  S_t]))`` against the main attention's own probabilities, the mean
  over its heads of ``exp(q_h . k_s * scale - lse_h)``; a custom VJP
  whose forward pass makes the gradients too (``dI = (softmax(I) -
  pbar) / T`` on ``S_t``), a tile of pairs at a time: no ``[heads, S,
  S]`` and no ``[S, S]`` array.

The packed mask, ``[B, S / 32, S]`` int32, queries along the LAST
dimension as the flash kernels hold their scores (keys down the
sublanes, queries along the lanes).  A tile of ``key_tile`` keys is
``key_tile / 32`` rows of words; bit ``b`` of row ``r`` of tile ``c`` is
key ``c * key_tile + b * (key_tile / 32) + r``: a kernel unpacks a tile
with 32 shifts of the tile's words, each giving ``key_tile / 32``
consecutive sublanes of the tile, and no value crosses a sublane.

Two forms of each: XLA's, a walk over blocks of ``QUERY_BLOCK`` queries
(the CPU, ``attention_impl="einsum"``, a given selection's
:func:`selected_lse`), the selection a plain ``jax.lax.top_k``; and on a
TPU the Pallas kernels ``hvd_dsa_select`` and ``hvd_dsa_indexer_loss``
(``ops/pallas_dsa.py``, imported where a kernel is asked for: Pallas
takes a second to import and a start that runs no kernel does not pay
it).
Index products take their operands as they arrive (bfloat16 in the
models) and accumulate in float32; thresholds, statistics and the loss
are float32.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Keys a tile of the packed mask holds: the flash kernels' key tile
# (the published ``kv_chunk_size``), so that a tile of scores reads
# whole rows of words.
KEY_TILE = 512
# Queries the XLA forms walk at a time (the published ``q_chunk_size``).
QUERY_BLOCK = 512
# Queries a grid step of ``hvd_dsa_select`` holds against every key up
# to its last: their scores [S, 128] float32 are 8 MiB of VMEM at 16384.
SELECT_QUERIES = 128
# Queries a grid step of ``hvd_dsa_indexer_loss`` holds: every head's
# queries of the main attention are 4 MiB of VMEM at 512 x 32 x 128.
LOSS_QUERIES = 512
BITS = 32
INT_MIN = -(1 << 31)

# What a recomputed layer may keep (``checkpoint_name``): the packed
# mask and the selected scores' log-sum-exp (making them again is the
# selection over again), and the alignment loss's gradients, which its
# forward pass makes.
SELECTED_NAME = "dsa_selected"
LSE_NAME = "dsa_lse"
GRADS_NAME = "dsa_indexer_grads"
DSA_NAMES = (SELECTED_NAME, LSE_NAME, GRADS_NAME)


def key_tile_of(seq: int) -> int:
    """The packed mask's tile at ``seq`` keys: ``KEY_TILE``, or for a
    shorter or odd sequence the most keys up to it that divide ``seq``
    in whole words."""
    if seq % BITS:
        raise ValueError("a packed mask holds keys in words of %d: a "
                         "sequence of %d is not whole words" % (BITS, seq))
    return max(tile for tile in range(BITS, min(KEY_TILE, seq) + 1, BITS)
               if seq % tile == 0)


def pack_mask(keep, key_tile: Optional[int] = None):
    """``keep`` ``[B, Sq, Sk]`` bool as the packed mask ``[B, Sk / 32,
    Sq]`` int32 (the layout above)."""
    batch, sq, sk = keep.shape
    key_tile = key_tile or key_tile_of(sk)
    rows = key_tile // BITS
    bits = keep.reshape(batch, sq, sk // key_tile, BITS, rows)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(BITS, dtype=jnp.uint32))
    words = (bits.astype(jnp.uint32)
             * weights[None, None, None, :, None]).sum(3, dtype=jnp.uint32)
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    return words.reshape(batch, sq, sk // BITS).transpose(0, 2, 1)


def unpack_mask(packed, key_tile: Optional[int] = None):
    """``pack_mask``'s inverse: ``[B, Sk / 32, Sq]`` int32 as ``[B, Sq,
    Sk]`` bool."""
    batch, words, sq = packed.shape
    sk = words * BITS
    key_tile = key_tile or key_tile_of(sk)
    rows = key_tile // BITS
    tiles = packed.transpose(0, 2, 1).reshape(batch, sq, sk // key_tile, 1,
                                              rows)
    shifts = jnp.arange(BITS, dtype=jnp.int32)[None, None, None, :, None]
    bits = jnp.right_shift(tiles, shifts) & 1
    return bits.reshape(batch, sq, sk) != 0


def unpack_tile(words):
    """Inside a kernel: one tile's words ``[keys / 32, queries]`` int32
    as the tile's ``[keys, queries]`` bool, by 32 shifts, each giving
    ``keys / 32`` consecutive rows."""
    return jnp.concatenate([(words >> bit) & 1 for bit in range(BITS)],
                           axis=0) != 0


def selected_pairs(seq: int, topk: int) -> int:
    """Pairs of query and key a sequence's selection holds: every causal
    key of the first ``topk`` queries, ``topk`` a query after them."""
    inside = min(topk, seq)
    return inside * (inside + 1) // 2 + (seq - inside) * inside


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def select_bytes(sequences: int, seq: int) -> int:
    """Bytes one layer's selection writes in HBM on one device in its
    forward pass, for the passes after it to read: the packed mask, a
    bit a pair, and the selected scores' log-sum-exp, a float32 a query.
    The index scores themselves live a block of queries at a time (in
    VMEM where the kernel runs, one block of ``QUERY_BLOCK`` rows in
    XLA's form) and are not among them."""
    return sequences * (seq // BITS * seq * 4 + seq * 4)


def _query_blocks(seq: int) -> int:
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError("the walk takes whole blocks of %d queries: %d "
                         "positions are not" % (block, seq))
    return block


def _block_scores(q_b, k_i, w_b):
    """``I`` of a block of queries against every key, ``[block, S]``
    float32, the causal mask not applied."""
    dots = jnp.einsum("tjd,sd->tjs", q_b, k_i,
                      preferred_element_type=jnp.float32)
    return (jax.nn.relu(dots) * w_b.astype(jnp.float32)[:, :, None]).sum(1)


def _rows_of(a, start, block):
    return jax.lax.dynamic_slice_in_dim(a, start, block, axis=0)


def _select_one(q_i, k_i, w, topk: int, key_tile: int):
    """One sequence: ``(packed [S / 32, S], lse [S])``."""
    seq = q_i.shape[0]
    block = _query_blocks(seq)
    key_at = jnp.arange(seq)

    def one(start):
        scores = _block_scores(_rows_of(q_i, start, block), k_i,
                               _rows_of(w, start, block))
        causal = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        _, taken = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                                 min(topk, seq))
        keep = jnp.zeros((block, seq), bool).at[
            jnp.arange(block)[:, None], taken].set(True) & causal
        lse = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return pack_mask(keep[None], key_tile)[0], lse
    packed, lse = jax.lax.map(one, jnp.arange(0, seq, block))
    return (jnp.moveaxis(packed, 0, 1).reshape(seq // BITS, seq),
            lse.reshape(seq))


def _selected_lse_one(q_i, k_i, w, packed, key_tile: int):
    seq = q_i.shape[0]
    block = _query_blocks(seq)

    def one(start):
        scores = _block_scores(_rows_of(q_i, start, block), k_i,
                               _rows_of(w, start, block))
        keep = unpack_mask(jax.lax.dynamic_slice_in_dim(
            packed, start, block, axis=1)[None], key_tile)[0]
        return jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jax.lax.map(one, jnp.arange(0, seq, block)).reshape(seq)


def selected_lse(q_i, k_i, w, packed, key_tile: Optional[int] = None):
    """``logsumexp(I[t, S_t])`` ``[B, S]`` float32 for a selection that
    is GIVEN (``select`` hands its own out with the mask); all-masked
    rows read ``-inf``."""
    key_tile = key_tile or key_tile_of(q_i.shape[1])
    return jax.vmap(functools.partial(_selected_lse_one, key_tile=key_tile))(
        q_i, k_i, w, packed)


def select(q_i, k_i, w, topk: int, *, kernels: bool = False,
           interpret: bool = False, key_tile: Optional[int] = None):
    """``(packed [B, S / 32, S] int32, lse [B, S] float32)`` of ``q_i``
    ``[B, S, J, D]``, ``k_i`` ``[B, S, D]`` and ``w`` ``[B, S, J]``: the
    selection above and the log-sum-exp of the selected scores.  No
    gradient flows through either.  ``kernels``: the Pallas kernel
    (``interpret`` runs its body in the interpreter)."""
    q_i, k_i, w = jax.lax.stop_gradient((q_i, k_i, w))
    seq = q_i.shape[1]
    key_tile = key_tile or key_tile_of(seq)
    with jax.named_scope("select"):
        if kernels or interpret:
            from .pallas_dsa import select_call
            packed, lse = select_call(q_i, k_i, w.astype(jnp.float32),
                                      topk=int(topk), key_tile=key_tile,
                                      interpret=interpret)
        else:
            packed, lse = jax.vmap(functools.partial(
                _select_one, topk=int(topk), key_tile=key_tile))(q_i, k_i, w)
        return (checkpoint_name(packed, SELECTED_NAME),
                checkpoint_name(lse, LSE_NAME))


# -- the alignment loss ---------------------------------------------------------

def _loss_one(q_i, k_i, w, q, k, lse, packed, lse_i, scale: float,
              key_tile: int):
    """One sequence: the sum over its queries of ``KL(pbar || softmax(I))``
    on ``S_t``, and that sum's gradients to ``q_i``, ``k_i`` and ``w``.
    ``q`` ``[S, H, D]`` and ``k`` ``[S, KV, D]`` are the main
    attention's, ``lse`` ``[H, S]`` its log-sum-exp over ``S_t``,
    ``lse_i`` ``[S]`` the selected index scores'."""
    seq, heads = q.shape[:2]
    kv_heads = k.shape[1]
    block = _query_blocks(seq)
    grouped_lse = lse.reshape(kv_heads, heads // kv_heads, seq)

    def one(d_k, start):
        q_b, w_b = _rows_of(q_i, start, block), _rows_of(w, start, block)
        scores, back = jax.vjp(_block_scores, q_b, k_i, w_b)
        keep = unpack_mask(jax.lax.dynamic_slice_in_dim(
            packed, start, block, axis=1)[None], key_tile)[0]
        # The scale rides on the queries, in their dtype, as the flash
        # kernels that made ``lse`` have it.
        main = (_rows_of(q, start, block) * scale).astype(q.dtype).reshape(
            block, kv_heads, heads // kv_heads, -1)
        lse_b = jax.lax.dynamic_slice_in_dim(grouped_lse, start, block, 2)

        def add_group(total, of_group):
            q_g, k_g, lse_g = of_group      # [block, G, D], [S, D], [G, block]
            s = jnp.einsum("tgd,sd->gts", q_g, k_g,
                           preferred_element_type=jnp.float32)
            return total + jnp.exp(s - lse_g[:, :, None]).sum(0), None
        pbar, _ = jax.lax.scan(
            add_group, jnp.zeros((block, seq), jnp.float32),
            (jnp.moveaxis(main, 1, 0), jnp.moveaxis(k, 1, 0), lse_b))
        pbar = jnp.where(keep, pbar / heads, 0.0)
        log_q = scores - _rows_of(lse_i, start, block)[:, None]
        kl = jnp.where(pbar > 0.0,
                       pbar * (jnp.log(jnp.where(pbar > 0.0, pbar, 1.0))
                               - log_q), 0.0).sum(-1)
        d_scores = jnp.where(keep, jnp.exp(log_q) - pbar, 0.0)
        d_q, d_k_b, d_w = back(d_scores)
        return d_k + d_k_b.astype(jnp.float32), (kl, d_q, d_w)
    d_k, (kl, d_q, d_w) = jax.lax.scan(
        one, jnp.zeros(k_i.shape, jnp.float32), jnp.arange(0, seq, block))
    return (kl.sum(), d_q.reshape(q_i.shape), d_k.astype(k_i.dtype),
            d_w.reshape(w.shape))


def _loss_and_grads(q_i, k_i, w, q, k, lse, packed, lse_i, scale, kernels,
                    interpret, key_tile):
    with jax.named_scope("indexer_loss"):
        rows = q_i.shape[0] * q_i.shape[1]
        if kernels or interpret:
            from .pallas_dsa import loss_call
            total, *grads = loss_call(q_i, k_i, w, q, k, lse, packed, lse_i,
                                      scale=scale, key_tile=key_tile,
                                      interpret=interpret)
        else:
            total, *grads = jax.vmap(functools.partial(
                _loss_one, scale=scale, key_tile=key_tile))(
                    q_i, k_i, w, q, k, lse, packed, lse_i)
            total = total.sum()
        grads = tuple(checkpoint_name((g / rows).astype(g.dtype), GRADS_NAME)
                      for g in grads)
        return total / rows, grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _indexer_loss(q_i, k_i, w, q, k, lse, packed, lse_i, scale, kernels,
                  interpret, key_tile):
    return _loss_and_grads(q_i, k_i, w, q, k, lse, packed, lse_i, scale,
                           kernels, interpret, key_tile)[0]


def _indexer_loss_fwd(q_i, k_i, w, q, k, lse, packed, lse_i, scale, kernels,
                      interpret, key_tile):
    return _loss_and_grads(q_i, k_i, w, q, k, lse, packed, lse_i, scale,
                           kernels, interpret, key_tile)


def _indexer_loss_bwd(scale, kernels, interpret, key_tile, grads, g):
    del scale, kernels, interpret, key_tile
    # The main attention's arrays, the mask and the statistics are
    # constants of this loss: the model hands them over detached.
    return tuple((g * d).astype(d.dtype) for d in grads) + (None,) * 5


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def indexer_loss(q_i, k_i, w, q, k, lse, packed, lse_i, scale: float, *,
                 kernels: bool = False, interpret: bool = False,
                 key_tile: Optional[int] = None):
    """``mean over [B, S] of KL(pbar[t, S_t] || softmax(I[t, S_t]))``,
    differentiable in ``q_i`` ``[B, S, J, D]``, ``k_i`` ``[B, S, D]``
    and ``w`` ``[B, S, J]`` alone.  ``q`` ``[B, S, H, D]`` and ``k``
    ``[B, S, KV, D]`` are the main attention's queries and keys (each
    key head serving ``H / KV`` consecutive query heads), ``lse`` ``[B,
    H, S]`` its log-sum-exp over ``S_t`` at ``scale``, ``packed`` the
    selection and ``lse_i`` ``[B, S]`` ``logsumexp(I[t, S_t])``
    (:func:`select`'s, or :func:`selected_lse`'s for a given
    selection).  ``pbar`` is the mean over the heads of ``exp(q_h . k_s
    scale - lse_h)``, which sums to one over ``S_t`` as each head's
    does."""
    key_tile = key_tile or key_tile_of(q_i.shape[1])
    q, k, lse, lse_i = jax.lax.stop_gradient((q, k, lse, lse_i))
    return _indexer_loss(q_i, k_i, w.astype(jnp.float32), q, k, lse, packed,
                         lse_i, float(scale), bool(kernels), bool(interpret),
                         int(key_tile))
