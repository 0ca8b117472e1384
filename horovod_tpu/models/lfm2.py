"""LFM2-MoE: a decoder whose layers differ twice over, by two lists: the
operator is a doubly gated short convolution or grouped-query attention
with normed, rotated queries and keys, and the feed-forward a dense
SwiGLU or top-k routed experts (Liquid AI's ``lfm2_moe`` family,
LiquidAI/LFM2-24B-A2B).  ``rms(x) = x / sqrt(mean(x^2) + eps)``; no bias
anywhere.

* Embedding ``h = E[ids]``, tied to the head: ``logits = (rms(h) *
  w_final) @ E^T``.
* Every layer: ``h += op(rms(h) * w_op)``, then ``h += ffn(rms(h) *
  w_ffn)``.
* ``conv`` operator: ``[B, C, x] = split(u @ W_in, 3)``; ``y = C *
  conv(B * x)`` with a causal depthwise convolution over ``conv_L_cache``
  positions; ``y @ W_out``.
* ``full_attention`` operator: query heads over fewer key-value heads,
  each serving a run of consecutive query heads; ``q = rms(q) * w_q``
  and ``k = rms(k) * w_k`` over each head's own width; rotary positions
  over the whole head in the rotate-half pairing; causal ``softmax(q
  k^T / sqrt(d)) v``.
* Dense ``ffn``: ``(silu(x @ W1) * (x @ W3)) @ W2``.
* Sparse ``ffn`` (``parallel/moe.py`` ``routed_experts``): sigmoid
  scores over ``num_experts``, the top ``num_experts_per_tok`` of score
  plus a selection bias chosen, their gates normalised; this model holds
  ``experts_held`` of the experts from ``first_expert`` on and computes
  their part of the layer, for every token that chose them.

TPU-first like ``granite.py`` (the norm, the gated MLP, the convolution,
the flash dispatch and the sparse feed-forward are ``layers.py``'s):
matmuls in ``dtype`` (bfloat16) from float32 parameters; the router, the
norms and the rotation in float32; with ``remat`` a layer is recomputed
in the backward pass but for the flash kernels' output, the routers'
choice and what the device has room for (``REMAT_CANDIDATES``).
Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.lfm2_partition_rules`.
"""

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding

from ..parallel import moe
from . import layers
from .layers import (FLASH_NAMES, GatedMLP, RMSNorm, SparseFFN,
                     causal_depthwise_conv, counts_by_expert,
                     grouped_causal_attention, mesh_of, prefixes, recomputed,
                     rotary_tables, rotate)

CONV, ATTENTION = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# What a recomputed layer may keep from its forward pass beside what the
# flash kernels name, dearest to recompute first: the dense SwiGLU's gate
# and up, the experts' gate and up (two grouped products), the
# convolution operator's input projection, the sorted rows (a gather).
# As many as fit the device are kept (``layers.kept_across_remat``).
MATMUL_NAMES = ("gate_up", moe.EXPERT_GATE_UP_NAME, "in_proj", moe.ROWS_NAME)
# Always kept: the kernels' output, and the routers' choice, which a
# recomputed pass must not make again (``parallel/moe.py``).
KEPT_NAMES = FLASH_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776       # the dense SwiGLU's width
    moe_intermediate_size: int = 1536    # a routed expert's
    # One operator and one feed-forward a layer.
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV, CONV, CONV)
    ffn_types: Tuple[str, ...] = (DENSE, DENSE) + (SPARSE,) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    # The router's width, and the run of experts this model holds.
    num_experts: int = 64
    num_experts_per_tok: int = 4
    experts_held: int = 64
    first_expert: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # As ``GraniteConfig``'s.
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}; an "
                             f"operator is '{CONV}' or '{ATTENTION}'")
        if (len(self.ffn_types) != len(self.layer_types)
                or set(self.ffn_types) - {DENSE, SPARSE}):
            raise ValueError("ffn_types gives each layer of layer_types "
                             f"'{DENSE}' or '{SPARSE}'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError("the experts held, first_expert to first_expert "
                             "+ experts_held - 1, lie among num_experts")
        if self.head_dim % 2:
            raise ValueError("rotary positions pair a head's halves")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def lfm2_tiny_config(**kw) -> LFM2Config:
    """Tiny stack for tests and dry runs: a dense convolution layer,
    then attention and convolution with routed experts; 4 query heads
    over 2 key-value heads; 8 experts of which 4 are held, top 2."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32,
                    layer_types=(CONV, ATTENTION, CONV),
                    ffn_types=(DENSE, SPARSE, SPARSE),
                    num_attention_heads=4, num_key_value_heads=2,
                    num_experts=8, num_experts_per_tok=2, experts_held=4)
    defaults.update(kw)
    return LFM2Config(**defaults)


class ShortConv(nn.Module):
    """``C * conv(B * x)`` between two projections."""
    config: LFM2Config

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        bcx = checkpoint_name(dense(3 * cfg.hidden_size, "in_proj")(u),
                              "in_proj")
        with jax.named_scope("conv"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (cfg.conv_L_cache, cfg.hidden_size),
                                jnp.float32)
            b, c, x = jnp.split(bcx, 3, axis=-1)
            y = c * causal_depthwise_conv(b * x, kernel, None)
        return dense(cfg.hidden_size, "out_proj")(y)


class RotaryAttention(nn.Module):
    """Grouped-query attention, queries and keys normed over their
    head and rotated."""
    config: LFM2Config
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        dense = lambda heads, name: nn.DenseGeneral(
            features=(heads, head_dim), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
        q = dense(q_heads, "query")(x)
        k = dense(kv_heads, "key")(x)
        v = dense(kv_heads, "value")(x)
        with jax.named_scope("rotary"):
            q = rotate(norm("query_norm")(q), cos, sin)
            k = rotate(norm("key_norm")(k), cos, sin)
        ctx = grouped_causal_attention(
            q, k, v, head_dim ** -0.5, cfg, self.heads_sharding,
            self.is_initializing())
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


def sparse_ffn(config: LFM2Config, mesh) -> SparseFFN:
    """The routed experts of a sparse layer: sigmoid scores plus a
    selection bias, no shared expert."""
    return SparseFFN(
        experts=config.num_experts, held=config.experts_held,
        first_expert=config.first_expert, top_k=config.num_experts_per_tok,
        width=config.moe_intermediate_size, normalize=config.norm_topk_prob,
        dtype=config.dtype, scale=config.routed_scaling_factor, mesh=mesh,
        name="moe")


class LFM2Layer(nn.Module):
    config: LFM2Config
    operator: str
    ffn: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
        u = norm("operator_norm")(x)
        if self.operator == CONV:
            x = x + ShortConv(cfg, name="conv")(u)
        else:
            x = x + RotaryAttention(cfg, self.heads_sharding,
                                    name="attention")(u, cos, sin)
        u = norm("ffn_norm")(x)
        if self.ffn == DENSE:
            return x + GatedMLP(cfg.intermediate_size, cfg.dtype,
                                name="mlp")(u)
        return x + sparse_ffn(cfg, mesh_of(self.heads_sharding))(u)


class LFM2LMHeadModel(nn.Module):
    """The stack and the tied head."""
    config: LFM2Config
    heads_sharding: Optional[NamedSharding] = None
    # What a recomputed layer keeps (``config.remat``); the step
    # builder hands over what fits its shapes and its device.
    remat_names: Tuple[str, ...] = REMAT_NAMES

    @nn.compact
    def hidden_and_embedding(self, input_ids):
        """The final hidden states ``[B, S, H]`` (after the last norm)
        and the token embedding ``[V, H]`` the head is tied to."""
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="word_embeddings")
        x = wte(input_ids)
        with jax.named_scope("rotary_tables"):   # once a step
            cos, sin = rotary_tables(input_ids.shape[1], cfg.head_dim,
                                     cfg.rope_theta)
        layer = recomputed(LFM2Layer, cfg.remat, self.remat_names)
        for i, (operator, ffn) in enumerate(zip(cfg.layer_types,
                                                cfg.ffn_types)):
            x = layer(cfg, operator, ffn, self.heads_sharding,
                      name=f"layer_{i}")(x, cos, sin)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        return x, wte.embedding

    def __call__(self, input_ids):
        x, embedding = self.hidden_and_embedding(input_ids)
        return jnp.einsum("bsh,vh->bsv", x,
                          embedding.astype(self.config.dtype),
                          preferred_element_type=jnp.float32)


expert_choices = functools.partial(layers.expert_choices, LFM2LMHeadModel)


def choice_counts(config: LFM2Config, params, input_ids):
    """``{layer index: [num_experts] int32}``: a batch's choices by
    expert, in every sparse layer."""
    return {i: counts_by_expert(chosen, config.num_experts)
            for i, chosen in expert_choices(config, params,
                                            input_ids).items()}


def remat_bytes(names, sequences: int, seq: int, config: LFM2Config) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it.  Tensor parallelism is not
    counted: the figure errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    operators, ffns = config.layer_types, config.ffn_types
    rows = moe.dispatch_rows(1, config.num_experts_per_tok,
                             config.experts_held)   # of one token
    per_token = {
        "flash_out": operators.count(ATTENTION) * config.hidden_size
        * itemsize,
        "flash_lse": operators.count(ATTENTION) * config.num_attention_heads
        * 4,
        moe.CHOICE_NAME: ffns.count(SPARSE) * config.num_experts_per_tok * 4,
        "gate_up": ffns.count(DENSE) * 2 * config.intermediate_size
        * itemsize,
        moe.EXPERT_GATE_UP_NAME: ffns.count(SPARSE) * rows * 2
        * config.moe_intermediate_size * itemsize,
        "in_proj": operators.count(CONV) * 3 * config.hidden_size * itemsize,
        moe.ROWS_NAME: ffns.count(SPARSE) * rows * config.hidden_size
        * itemsize}
    return sequences * seq * sum(per_token[name] for name in names)
