"""The selective state-space recurrence of Mamba-2 in its chunked form
(SSD: Dao and Gu 2024, arXiv:2405.21060, section 6).

The recurrence, per head ``h`` with a scalar decay, state ``S`` of
``[P, N]`` (head size by state size) and one group of ``B`` and ``C``
shared by all heads::

    S_t = exp(A_h * dt_t) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

is a scan over time.  Cut into chunks of ``chunk`` positions it becomes
matrix products: within a chunk, a causal ``chunk x chunk`` matrix of
``C_l . B_s`` weighted by the decay from ``s`` to ``l`` times the
chunk's inputs; at each chunk's end, the state its own inputs leave;
between chunks, the recurrence over those states (a scan of
``seq / chunk`` steps); and the entering state's contribution to every
position of the chunk.

Plain ``jax.numpy`` einsums, differentiable by autodiff.  ``dt``, the
decays, their cumulative sums and the carried state are float32; the
products take their operands in ``x``'s dtype (bf16 in the models) and
accumulate in float32.  The stages carry ``jax.named_scope``s so that a
device trace can tell them apart.
"""

from typing import Tuple

import jax
import jax.numpy as jnp


def chunks_of(seq: int, chunk: int) -> Tuple[int, int]:
    """``(count, length)`` of the chunks :func:`ssd_chunked` walks: a
    sequence shorter than ``chunk`` is one chunk of its own length; one
    that ``length`` does not divide is padded at its end (a padded
    position has ``dt`` = 0: it neither decays the state nor adds to
    it, and its output is cut off)."""
    length = min(chunk, seq)
    return -(-seq // length), length


def scan_bytes(batch: int, seq: int, heads: int, head_dim: int,
               state: int, chunk: int, itemsize: int) -> int:
    """Bytes of the arrays :func:`ssd_chunked` materialises for one
    layer's forward pass with ``batch`` sequences on the device: the
    decay between every two positions of a chunk (float32) and the
    weights made of it (in the compute dtype), both ``[batch, heads,
    count, length, length]``; the chunks' ``C . B`` products (float32,
    shared by the heads); and the states the chunks leave and the
    states they are entered with, ``[batch, count, heads, head_dim,
    state]`` float32 each."""
    count, length = chunks_of(seq, chunk)
    square = batch * count * length * length
    states = batch * count * heads * head_dim * state
    return square * heads * (4 + itemsize) + square * 4 + 2 * states * 4


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """``y`` of the recurrence above, ``[batch, seq, heads, head_dim]``
    in ``x``'s dtype, from ``S_{-1} = 0``.

    ``x``: ``[batch, seq, heads, head_dim]``; ``dt``: ``[batch, seq,
    heads]``, positive (after its softplus); ``a``: ``[heads]``,
    negative (``-exp(A_log)``); ``b``, ``c``: ``[batch, seq, state]``.
    """
    batch, seq, heads, head_dim = x.shape
    count, length = chunks_of(seq, chunk)
    pad = count * length - seq
    dtype = x.dtype
    dt = dt.astype(jnp.float32)

    def chunked(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(batch, count, length, *t.shape[2:])
    x, dt, b, c = chunked(x), chunked(dt), chunked(b), chunked(c)

    with jax.named_scope("decay"):
        # log of the decay a position applies, and its running sum
        # within the chunk (inclusive), heads before positions:
        # [batch, heads, count, length]
        log_a = jnp.transpose(dt * a.astype(jnp.float32), (0, 3, 1, 2))
        cum = jnp.cumsum(log_a, axis=-1)
        dt_h = jnp.transpose(dt, (0, 3, 1, 2))

    with jax.named_scope("intra_chunk"):
        # Position l reads position s <= l of its chunk through
        # (C_l . B_s) * exp(cum_l - cum_s) * dt_s.  Above the diagonal
        # the difference is positive and may overflow: masked before
        # the exponential, so that its gradient is 0 and not nan.
        scores = jnp.einsum("bcln,bcsn->bcls", c, b,
                            preferred_element_type=jnp.float32)
        causal = jnp.tril(jnp.ones((length, length), bool))
        between = jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                            -jnp.inf)
        weights = (jnp.exp(between) * scores[:, None]
                   * dt_h[..., None, :]).astype(dtype)
        y = jnp.einsum("bhcls,bcshp->bclhp", weights, x,
                       preferred_element_type=jnp.float32)

    with jax.named_scope("chunk_states"):
        # What a chunk's own inputs leave at its end:
        # sum_s exp(cum_end - cum_s) * dt_s * x_s (outer) B_s
        to_end = jnp.exp(cum[..., -1:] - cum) * dt_h     # [b, h, c, l]
        weighted = (x * jnp.transpose(to_end, (0, 2, 3, 1))[..., None]
                    ).astype(dtype)
        local = jnp.einsum("bcshp,bcsn->bchpn", weighted, b,
                           preferred_element_type=jnp.float32)

    with jax.named_scope("state_scan"):
        # The recurrence between chunks: each is entered with the state
        # its predecessors left, decayed over the chunk before it.
        chunk_decay = jnp.exp(cum[..., -1])              # [b, h, c]

        def step(carried, of_chunk):
            decay, left = of_chunk
            return carried * decay[..., None, None] + left, carried
        _, entered = jax.lax.scan(
            step, jnp.zeros((batch, heads, head_dim, b.shape[-1]),
                            jnp.float32),
            (jnp.moveaxis(chunk_decay, 2, 0), jnp.moveaxis(local, 1, 0)))
        entered = jnp.moveaxis(entered, 0, 1)            # [b, c, h, p, n]

    with jax.named_scope("state_output"):
        # The entering state as position l sees it: decayed by cum_l.
        from_state = jnp.einsum("bcln,bchpn->bclhp", c,
                                entered.astype(dtype),
                                preferred_element_type=jnp.float32)
        y = y + from_state * jnp.transpose(
            jnp.exp(cum), (0, 2, 3, 1))[..., None]

    y = y.reshape(batch, count * length, heads, head_dim)[:, :seq]
    return y.astype(dtype)
