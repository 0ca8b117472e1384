"""Sequence/context-parallel attention over mesh axes.

The reference has no sequence parallelism (SURVEY §2.3: SP/CP absent;
its ``alltoall`` collective is the primitive Ulysses-style SP builds
on).  On TPU long-context attention is first-class, in two idiomatic
forms:

* :func:`ring_attention` — blockwise attention with online (flash-
  style) softmax accumulation while K/V blocks rotate around the mesh
  axis ring via ``ppermute`` (ICI-neighbor transfers overlap with the
  per-block matmuls; memory stays O(S_local)).
* :func:`ulysses_attention` — all-to-all reshuffle from sequence-sharded
  to head-sharded, full attention per head group, all-to-all back
  (2 all-to-alls, best when heads ≥ axis size and ICI all-to-all is
  cheap).

Both are called inside ``jax.shard_map`` with the sequence dimension
sharded over ``axis_name``; both match full (unsharded) softmax
attention numerically, including causal masking with global positions.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ._compat import pvary as _pvary


def _block_scores(q, k, scale):
    # q: [B, Sq, H, D], k: [B, Skv, H, D] -> [B, H, Sq, Skv] in f32
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   window: Optional[int] = None) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Shapes (per shard): q/k/v ``[B, S_local, H, D]``; returns
    ``[B, S_local, H, D]``.  K/V rotate around the ring; softmax is
    accumulated online with the running-max trick, so the result is
    exact (not approximate) regardless of ring size.  A ``window`` is
    refused: the ring passes every shard of keys by every shard of
    queries and has no band yet (``ops/pallas_attention.py`` has, on
    one chip's sequence).
    """
    if window is not None:
        raise NotImplementedError(
            "ring_attention has no window: a band of %r keys across "
            "shards of the sequence is not written (ROADMAP.md, Queue 2)"
            % (window,))
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    B, Sq, H, D = q.shape
    Skv = k.shape[1]

    # Running accumulators in f32: m (max), l (denominator), o (weighted
    # values).
    # pvary: mark the accumulators as device-varying over the axis so
    # the scan carry type matches its (q-dependent, hence varying)
    # updates under shard_map's varying-axis typing.
    m = _pvary(jnp.full((B, H, Sq), -jnp.inf, dtype=jnp.float32),
                  axis_name)
    l = _pvary(jnp.zeros((B, H, Sq), dtype=jnp.float32), axis_name)
    o = _pvary(jnp.zeros((B, Sq, H, D), dtype=jnp.float32),
                  axis_name)

    q_pos = my_idx * Sq + jnp.arange(Sq)            # global q positions

    def step_fn(carry, step):
        m, l, o, k_blk, v_blk = carry
        # Block currently held arrived from rank (my_idx - step) mod n.
        src = (my_idx - step) % n
        s = _block_scores(q, k_blk, scale)          # [B,H,Sq,Skv]
        if causal:
            k_pos = src * Skv + jnp.arange(Skv)     # global k positions
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)               # [B,H,Sq]
        m_new = jnp.maximum(m, blk_max)
        # Guard fully-masked blocks (all -inf): exp(-inf - -inf) -> use
        # a finite stand-in; their weights are zero anyway.
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])          # [B,H,Sq,Skv]
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0,
                         jnp.exp(m - m_safe))       # rescale old acc
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk,
                        preferred_element_type=jnp.float32)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        # Rotate K/V one hop around the ring (ICI neighbor transfer,
        # overlapped by XLA with the next block's compute).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (m_new, l_new, o_new, k_next, v_next), None

    (m, l, o, _, _), _ = lax.scan(
        step_fn, (m, l, o, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """Exact attention via the Ulysses all-to-all reshuffle.

    Per-shard shapes ``[B, S_local, H, D]`` with ``H`` divisible by the
    axis size.  Sequence-sharded tensors are all-to-all'd into
    head-sharded full-sequence tensors, attended normally, and
    reshuffled back — two ``lax.all_to_all`` per tensor, the pattern the
    reference's alltoall collective exists to serve (SURVEY §2.3 EP/SP
    rows).
    """
    n = lax.psum(1, axis_name)
    B, S_local, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def to_headsharded(x):
        # [B, S_local, H, D] -> [B, S_global, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seqsharded(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = (to_headsharded(t) for t in (q, k, v))
    s = _block_scores(qh, kh, scale)                # [B,h,Sg,Sg]
    if causal:
        Sg = qh.shape[1]
        pos = jnp.arange(Sg)
        mask = pos[:, None] >= pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh,
                     preferred_element_type=jnp.float32)
    return to_seqsharded(out.astype(q.dtype))


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None):
    """Unsharded full attention (test oracle and single-device path)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = _block_scores(q, k, scale)
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
