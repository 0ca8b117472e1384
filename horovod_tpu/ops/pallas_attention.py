"""Flash attention as a Pallas TPU kernel.

The hot op of transformer training, written TPU-first: Q/K/V blocks
stream HBM→VMEM, scores hit the MXU per (q-block, kv-block) tile, and
the softmax is accumulated online in VMEM scratch across the kernel
grid's sequential last dimension (the canonical TPU flash pattern —
grid iterations over kv blocks execute in order per q block, so the
running max / denominator / weighted-sum live in scratch between
iterations).

Pairs with the mesh-level sequence parallelism in
:mod:`horovod_tpu.parallel.attention`: ring attention rotates K/V
shards between chips while THIS kernel computes each local block.

The public :func:`flash_attention` carries a custom VJP whose backward
recomputes attention in plain XLA (exact, O(S²) memory in backward;
kernelizing the backward is a further optimization).  The kernel is
compiled by Mosaic unless the caller passes ``interpret=True``, which
is how the CPU tests run the same kernel body.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, bq: int, bk: int,
                 skv: int):
    i = pl.program_id(1)          # q-block index
    j = pl.program_id(2)          # kv-block index (sequential)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: whole block is masked out when its lowest k position
    # exceeds this q block's highest position.
    run = True
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0].astype(jnp.float32)              # [bk, D]
        v = v_ref[0].astype(jnp.float32)              # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        kpos = j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        if skv % bk != 0:
            # Tail block: positions past the sequence end are padding.
            s = jnp.where(kpos < skv, s, NEG_INF)
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:, 0]                          # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])               # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                # [bq]
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new[:, None]
        l_ref[:] = l_new[:, None]

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def _flash_fwd(q, k, v, scale: float, causal: bool, bq: int, bk: int,
               interpret: bool):
    """q/k/v: [BH, S, D] → [BH, S, D]."""
    BH, Sq, D = q.shape
    Skv = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    # Pallas clamps partial blocks to fit, which would mis-position the
    # tail; pad to block multiples instead (the key mask hides padded
    # keys; padded q rows are sliced off the output).
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    nq = Sq_p // bq
    nk = Skv_p // bk

    scratch = [pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, D), jnp.float32)]

    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, skv=Skv)
    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq_p, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq] if pad_q else out


def _ref_attn_bhsd(q, k, v, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return p, jnp.einsum("bqk,bkd->bqd", p,
                         v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, bq, bk, interpret):
    return _flash_fwd(q, k, v, scale, causal, bq, bk, interpret)


def _flash_vjp_fwd(q, k, v, scale, causal, bq, bk, interpret):
    out = _flash_fwd(q, k, v, scale, causal, bq, bk, interpret)
    return out, (q, k, v)


def _flash_vjp_bwd(scale, causal, bq, bk, interpret, res, do):
    q, k, v = res
    p, _ = _ref_attn_bhsd(q, k, v, scale, causal)
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bqk,bqd->bkd", p, do32)
    dp = jnp.einsum("bqd,bkd->bqk", do32, v.astype(jnp.float32))
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bqk,bkd->bqd", ds,
                    k.astype(jnp.float32)) * scale
    dk = jnp.einsum("bqk,bqd->bkd", ds,
                    q.astype(jnp.float32)) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Flash attention on ``[B, S, H, D]`` tensors.

    The kernel is compiled for the TPU; off the TPU that fails loudly.
    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (the CPU tests ask for it by name).
    """
    B, Sq, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    out = _flash(bhsd(q), bhsd(k), bhsd(v), float(scale), bool(causal),
                 int(block_q), int(block_k), bool(interpret))
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
