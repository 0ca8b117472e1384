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

TPU-first like ``granite.py``, whose norm, gated MLP, convolution and
flash dispatch it imports: matmuls in ``dtype`` (bfloat16) from float32
parameters; the router, the norms and the rotation in float32; with
``remat`` a layer is recomputed in the backward pass but for the flash
kernels' output, the routers' choice and what :func:`remat_names` finds
room for.  Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.lfm2_partition_rules`.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding

from ..parallel import moe
from .gpt import FLASH_NAMES, _flash_causal, attention_impl
from .granite import GatedMLP, RMSNorm, causal_depthwise_conv

CONV, ATTENTION = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# What a recomputed layer may keep from its forward pass beside what the
# flash kernels name, dearest to recompute first: the dense SwiGLU's gate
# and up, the experts' gate and up (two grouped products), the
# convolution operator's input projection, the sorted rows (a gather).
# ``remat_names`` keeps as many as fit the device.
MATMUL_NAMES = ("gate_up", moe.EXPERT_GATE_UP_NAME, "in_proj", moe.ROWS_NAME)
# Always kept: the kernels' output, and the routers' choice, which a
# recomputed pass must not make again (``parallel/moe.py``).
KEPT_NAMES = FLASH_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES


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


def rotary_tables(seq: int, head_dim: int, theta: float):
    """``cos`` and ``sin`` of ``position x theta^(-2i / head_dim)``,
    each ``[seq, head_dim / 2]`` in float32."""
    inverse = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inverse
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """Rotary positions in the rotate-half pairing: channel ``i`` of a
    head turns with channel ``i + head_dim / 2``.  ``x``: ``[B, S,
    heads, head_dim]``; float32 inside, ``x``'s type out."""
    first, second = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1).astype(x.dtype)


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
        group, head_dim = q_heads // kv_heads, cfg.head_dim
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
        scale = head_dim ** -0.5
        mesh = (None if self.heads_sharding is None
                else self.heads_sharding.mesh)
        if attention_impl(cfg, mesh, not self.is_initializing()) == "flash":
            # As Granite's: each key-value head laid out once for every
            # query head it serves.
            ctx = _flash_causal(q, jnp.repeat(k, group, axis=2),
                                jnp.repeat(v, group, axis=2),
                                self.heads_sharding, scale=scale)
            ctx = ctx.astype(cfg.dtype)
        else:
            seq = x.shape[1]
            q = q.reshape(*q.shape[:2], kv_heads, group, head_dim)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * scale
            causal = jnp.tril(jnp.ones((seq, seq), bool))
            scores = jnp.where(causal, scores, jnp.finfo(cfg.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(cfg.dtype), v)
            ctx = ctx.reshape(*ctx.shape[:2], q_heads, head_dim)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


def mesh_of(sharding: Optional[NamedSharding]) -> Optional[Mesh]:
    """The mesh a step builder's sharding lies on; None where the model
    is applied directly."""
    return None if sharding is None else sharding.mesh


class RoutedExperts(nn.Module):
    """The parameters of ``parallel.moe.routed_experts``: a router over
    all experts, and the stacked matrices of the experts held.  On one
    TPU device (the platform read as the attention's is) the layer's
    wide passes are Pallas kernels; ``init`` wants the parameters'
    shapes and nothing of the layer, so no kernel is traced for it."""
    config: LFM2Config
    # The mesh the step this model is traced in lays its arrays on (the
    # step builder says, through ``heads_sharding``); None where the
    # model is applied directly.
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
        stacked = lambda name, fan_in, fan_out: self.param(
            name, nn.initializers.lecun_normal(batch_axis=(0,)),
            (cfg.experts_held, fan_in, fan_out), jnp.float32)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (hidden, cfg.num_experts), jnp.float32)
        # A buffer in the published model: it selects, no gradient
        # reaches it, and no rule here moves it.
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        y, routing = moe.routed_experts(
            x.reshape(-1, hidden), router, bias,
            stacked("gate", hidden, width), stacked("up", hidden, width),
            stacked("down", width, hidden),
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            normalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            kernels=moe.on_one_tpu(self.mesh) and not self.is_initializing())
        self.sow("intermediates", "chosen", routing.chosen)
        return y.reshape(x.shape)


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
            return x + GatedMLP(cfg, name="mlp")(u)
        return x + RoutedExperts(cfg, mesh_of(self.heads_sharding),
                                 name="moe")(u)


class LFM2LMHeadModel(nn.Module):
    """The stack and the tied head."""
    config: LFM2Config
    heads_sharding: Optional[NamedSharding] = None
    # What a recomputed layer keeps (``config.remat``); the step
    # builder hands over what ``remat_names`` chose for its shapes.
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
        layer = LFM2Layer
        if cfg.remat:
            layer = nn.remat(
                LFM2Layer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *self.remat_names))
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


def sown_choices(model: nn.Module, params, input_ids):
    """``{layer index: [T, top_k] int32}``: what the ``moe`` module of
    every sparse ``layer_<i>`` of ``model`` sowed as ``chosen`` on
    ``input_ids`` (the stacks of this family and of the families built
    on its routed experts)."""
    _, state = model.apply(
        {"params": params}, input_ids, mutable=["intermediates"],
        method="hidden_and_embedding")
    return {int(name.split("_")[1]): layer["moe"]["chosen"][0]
            for name, layer in state["intermediates"].items()}


def expert_choices(config: LFM2Config, params, input_ids):
    """``{layer index: [T, top_k] int32}``: the experts, of all
    ``num_experts``, that each token chose in every sparse layer."""
    return sown_choices(
        LFM2LMHeadModel(dataclasses.replace(config, remat=False)), params,
        input_ids)


def counts_by_expert(chosen, num_experts: int):
    """``[num_experts] int32``: how many of the ``T x top_k`` choices
    ``chosen`` fell on each expert."""
    return jnp.bincount(chosen.reshape(-1), length=num_experts)


def choice_counts(config: LFM2Config, params, input_ids):
    """``{layer index: [num_experts] int32}``: a batch's choices by
    expert, in every sparse layer."""
    return {i: counts_by_expert(chosen, config.num_experts)
            for i, chosen in expert_choices(config, params,
                                            input_ids).items()}


def remat_bytes(names, tokens: int, config: LFM2Config) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``tokens`` of the batch on it.  Tensor parallelism is not counted:
    the figure errs high."""
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
    return tokens * sum(per_token[name] for name in names)


def remat_names(tokens: int, config: LFM2Config, state_bytes: int,
                memory_limit: Optional[int]) -> Tuple[str, ...]:
    """As ``models.granite.remat_names``: the kernels' names and as many
    of ``MATMUL_NAMES``, in their order, as fit one device's
    ``memory_limit`` bytes beside the state the step is handed and a
    margin of a quarter of the memory; every name where the device
    reports no limit."""
    if memory_limit is None:
        return REMAT_NAMES
    for count in range(len(REMAT_NAMES), len(KEPT_NAMES), -1):
        names = REMAT_NAMES[:count]
        if (remat_bytes(names, tokens, config) + state_bytes
                + memory_limit // 4 <= memory_limit):
            return names
    return KEPT_NAMES
