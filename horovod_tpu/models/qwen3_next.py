"""Qwen3-Next: a decoder whose mixers are of two kinds, gated
delta-rule linear attention in three layers of four and gated softmax
attention in the fourth, with a sparse feed-forward in every layer
(``model_type`` ``qwen3_next``, as Qwen/Qwen3-Next-80B-A3B-Instruct
publishes it; the linear layer is Gated DeltaNet, Yang, Kautz and
Hatamizadeh 2024, arXiv:2412.06464).  ``rms(x) = x / sqrt(mean(x^2) +
eps)``; the model's norms multiply by ``(1 + w)``, ``w`` from zeros;
no bias in any projection.

* Embedding ``h = E[ids]``; the head is a matrix of its own: ``logits =
  (rms(h) (1 + w_final)) @ W_head``.
* Every layer: ``h += mixer(rms(h) (1 + w_1))``, then ``h +=
  ffn(rms(h) (1 + w_2))``.  Layer ``i`` is full attention where ``(i +
  1) % full_attention_interval == 0``, else the gated delta rule.
* Gated delta rule, ``linear_num_key_heads`` key heads serving
  ``linear_num_value_heads`` value heads, each key head a run of
  consecutive value heads: ``[q | k | v | z | b | a] = u @ W_in``;
  ``[q | k | v]`` together through a causal depthwise convolution over
  ``linear_conv_kernel_dim`` positions, no bias, then ``silu``; ``beta
  = sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)`` a value
  head; ``q = q / sqrt(sum q^2 + 1e-6) / sqrt(d_k)``, ``k = k /
  sqrt(sum k^2 + 1e-6)``, a head at a time; the recurrence of
  ``ops/gated_delta.py``; ``o = rms(o) w_n silu(z)`` over each head,
  ``w_n`` a plain weight from ones; ``o @ W_out``.
* Full attention: ``[query | gate]`` of every head ``= u @ W_q``, split
  WITHIN the head; ``query = rms(query) (1 + w_qn)`` and ``k = rms(k)
  (1 + w_kn)`` over a head; rotary positions on the first
  ``partial_rotary_factor`` of a head's channels in the rotate-half
  pairing, the rest pass; causal ``softmax(q k^T / sqrt(d)) v``, each
  key-value head serving a run of consecutive query heads; ``(out *
  sigmoid(gate)) @ W_o``.
* Sparse ``ffn`` (``parallel/moe.py`` ``routed_experts`` under
  ``softmax_top_k``): ``p = softmax(x @ W_r)`` over ``num_experts``,
  the top ``num_experts_per_tok`` chosen, their gates ``p`` divided by
  their sum; plus ``sigmoid(x . w_s)`` times ONE SwiGLU of width
  ``shared_expert_intermediate_size`` on every token.  This model
  holds ``experts_held`` of the routed experts from ``first_expert``
  on and computes their part of the routed sum; the shared expert is
  whole on every chip.

TPU-first like ``deepseek_v3.py``: matmuls in ``dtype`` (bfloat16) from
float32 parameters; the router, the norms, the rotation, ``g`` and
``beta`` in float32; the recurrence in its chunked form with what
``ops/gated_delta.py`` keeps in float32.  On a TPU attention is the
Pallas flash kernels with keys and values repeated to the query heads;
elsewhere it is grouped einsums.  On ONE TPU device the delta rule's
chunks are solved by the Pallas kernels of ``ops/pallas_gated_delta.py``
where their tiles divide the widths, elsewhere by XLA's triangular
solve.  With ``remat`` a layer is recomputed
in the backward pass but for the flash kernels' output, the routers'
choice and what the device has room for (``REMAT_CANDIDATES``).
Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.qwen3_next_partition_rules`.
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

from ..ops import gated_delta
from ..parallel import moe
from . import layers
from .layers import (FLASH_NAMES, SparseFFN, causal_depthwise_conv,
                     grouped_causal_attention, mesh_of, prefixes, recomputed,
                     rotary_tables, rotate)
from .layers import given_choices  # noqa: F401  (the benchmark's name)

LINEAR, FULL = "linear_attention", "full_attention"
# Added under the root of a head's sum of squares (queries and keys of
# the delta rule), the class's own.
L2_NORM_EPS = 1e-6
# The class draws ``A`` uniformly from this range and keeps its log.
A_RANGE = (0.0, 16.0)
# What a recomputed layer may keep from its forward pass beside what the
# flash kernels name, dearest to recompute a byte first: the shared
# expert's gate and up; the delta rule's walk over the chunks (the
# states and ``v_new``: the one part of a layer that runs its steps one
# after another) and ``T``'s products; the delta-rule layers' input
# projection; the routed experts' gate and up (two grouped products
# over a buffer of which a sixteenth holds a pair), the sorted rows (a
# gather).  As many as fit the device are kept
# (``layers.kept_across_remat``).
MATMUL_NAMES = ("gate_up", gated_delta.STATES_NAME, gated_delta.WY_NAME,
                "in_proj", moe.EXPERT_GATE_UP_NAME, moe.ROWS_NAME)
# Always kept: the kernels' output, and the routers' choice, which a
# recomputed pass must not make again (``parallel/moe.py``).
KEPT_NAMES = FLASH_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # Full attention.
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # The gated delta rule.
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 64
    # The sparse feed-forward: the router's width, the run of routed
    # experts this model holds, the gated shared expert.
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    experts_held: int = 512
    first_expert: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # As ``GraniteConfig``'s.
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_key_heads must divide "
                             "linear_num_value_heads: each key head serves "
                             "a run of value heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError("the experts held, first_expert to first_expert "
                             "+ experts_held - 1, lie among num_experts")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary positions pair the halves of a part "
                             "of a head: head_dim x partial_rotary_factor "
                             "is even and at most head_dim")
        if self.full_attention_interval < 1:
            raise ValueError("full_attention_interval counts layers")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR
            for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def linear_in_proj_dim(self) -> int:
        """``[q | k | v | z | b | a]``."""
        return (self.linear_conv_dim + self.linear_value_dim
                + 2 * self.linear_num_value_heads)


def qwen3_next_tiny_config(**kw) -> Qwen3NextConfig:
    """Tiny stack for tests and dry runs: one period, three delta-rule
    layers and a full one; 2 key heads serving 4 value heads of 16; 4
    query heads over 2 key-value heads of 32 with 8 channels rotated;
    16 experts of which 4 are held, top 3; chunks of 32."""
    defaults = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, linear_num_key_heads=2,
                    linear_num_value_heads=4, linear_key_head_dim=16,
                    linear_value_head_dim=16, linear_chunk_size=32,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, num_experts=16,
                    num_experts_per_tok=3, experts_held=4)
    defaults.update(kw)
    return Qwen3NextConfig(**defaults)


class ZeroCentredRMSNorm(nn.Module):
    """``rms(x) (1 + w)``, ``w`` from zeros."""
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        return (x * (1.0 + scale)).astype(self.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


def l2_normed(x):
    """``x / sqrt(sum x^2 + 1e-6)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_NORM_EPS)


class GatedDeltaNet(nn.Module):
    """Gated delta-rule linear attention between two projections."""
    config: Qwen3NextConfig
    # How the step this model is traced in shards ``[B, S, heads, D]``;
    # None where the model is applied directly.
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        key_heads, value_heads = (cfg.linear_num_key_heads,
                                  cfg.linear_num_value_heads)
        d_k, d_v = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        keys, values = cfg.linear_key_dim, cfg.linear_value_dim
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        mixed = checkpoint_name(
            dense(cfg.linear_in_proj_dim, "in_proj")(x), "in_proj")
        qkv, z, b, a = jnp.split(
            mixed, [cfg.linear_conv_dim, cfg.linear_conv_dim + values,
                    cfg.linear_conv_dim + values + value_heads], axis=-1)
        heads = lambda t, n, d: t.reshape(*t.shape[:2], n, d)
        with jax.named_scope("conv"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (cfg.linear_conv_kernel_dim,
                                 cfg.linear_conv_dim), jnp.float32)
            qkv = nn.silu(causal_depthwise_conv(qkv, kernel, None))
            q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
            # Each key head is laid out once for every value head it
            # serves: from here on a head is a value head.
            serve = lambda t: jnp.repeat(heads(t, key_heads, d_k),
                                         value_heads // key_heads, axis=2)
            q = (l2_normed(serve(q)) * d_k ** -0.5).astype(cfg.dtype)
            k = l2_normed(serve(k)).astype(cfg.dtype)
            v = heads(v, value_heads, d_v)
            if self.heads_sharding is not None:
                q, k, v = (jax.lax.with_sharding_constraint(
                    t, self.heads_sharding) for t in (q, k, v))
        a_log = self.param("A_log", _a_log_init, (value_heads,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (value_heads,),
                             jnp.float32)
        with jax.named_scope("delta_rule"):
            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                a.astype(jnp.float32) + dt_bias)
            if self.heads_sharding is not None:
                # A head's two scalars lie where its keys and values do
                # (and their types carry the mesh that ``q``, ``k`` and
                # ``v`` carry, in every layer and pass: the kernels'
                # one trace hangs on it).
                of_heads = NamedSharding(
                    self.heads_sharding.mesh,
                    jax.sharding.PartitionSpec(*self.heads_sharding.spec[:3]))
                g, beta = (jax.lax.with_sharding_constraint(t, of_heads)
                           for t in (g, beta))
            o = gated_delta.gated_delta_chunked(
                q, k, v, g, beta, cfg.linear_chunk_size,
                kernels=(moe.on_one_tpu(mesh_of(self.heads_sharding))
                         and not self.is_initializing()))
        with jax.named_scope("gated_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (d_v,),
                               jnp.float32)
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            o = o * scale * nn.silu(
                heads(z, value_heads, d_v).astype(jnp.float32))
            o = o.astype(cfg.dtype).reshape(*o.shape[:2], values)
        return dense(cfg.hidden_size, "out_proj")(o)


def rotate_part(x, cos, sin):
    """Rotary positions on a head's first ``2 x cos.shape[-1]`` channels
    (``layers.rotate``'s pairing over that part); the others pass."""
    part = 2 * cos.shape[-1]
    return jnp.concatenate([rotate(x[..., :part], cos, sin), x[..., part:]],
                           axis=-1)


class GatedAttention(nn.Module):
    """Grouped-query attention, queries and keys normed over their head
    and partly rotated, the output gated a channel by a sigmoid that the
    query's projection also yields."""
    config: Qwen3NextConfig
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        dense = lambda heads, width, name: nn.DenseGeneral(
            features=(heads, width), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                               name=name)
        # A head's query and its gate, side by side WITHIN the head.
        q, gate = jnp.split(dense(q_heads, 2 * head_dim, "query")(x), 2,
                            axis=-1)
        k = dense(kv_heads, head_dim, "key")(x)
        v = dense(kv_heads, head_dim, "value")(x)
        with jax.named_scope("rotary"):
            q = rotate_part(norm("query_norm")(q), cos, sin)
            k = rotate_part(norm("key_norm")(k), cos, sin)
        ctx = grouped_causal_attention(
            q, k, v, head_dim ** -0.5, cfg, self.heads_sharding,
            self.is_initializing())
        with jax.named_scope("output_gate"):
            ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                cfg.dtype)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


def sparse_ffn(config: Qwen3NextConfig, mesh) -> SparseFFN:
    """The routed experts of a layer under a softmax router, and beside
    them the shared expert times a sigmoid of the token."""
    return SparseFFN(
        experts=config.num_experts, held=config.experts_held,
        first_expert=config.first_expert, top_k=config.num_experts_per_tok,
        width=config.moe_intermediate_size, normalize=config.norm_topk_prob,
        dtype=config.dtype, router=moe.softmax_top_k,
        shared=config.shared_expert_intermediate_size, shared_gate=True,
        mesh=mesh, name="moe")


class Qwen3NextLayer(nn.Module):
    config: Qwen3NextConfig
    kind: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                               name=name)
        u = norm("mixer_norm")(x)
        if self.kind == LINEAR:
            x = x + GatedDeltaNet(cfg, self.heads_sharding, name=LINEAR)(u)
        else:
            x = x + GatedAttention(cfg, self.heads_sharding,
                                   name="attention")(u, cos, sin)
        return x + sparse_ffn(cfg, mesh_of(self.heads_sharding))(
            norm("ffn_norm")(x))


class Qwen3NextLMHeadModel(nn.Module):
    """The stack and its untied head."""
    config: Qwen3NextConfig
    heads_sharding: Optional[NamedSharding] = None
    # What a recomputed layer keeps (``config.remat``); the step
    # builder hands over what fits its shapes and its device.
    remat_names: Tuple[str, ...] = REMAT_NAMES

    @nn.compact
    def hidden_and_embedding(self, input_ids):
        """The final hidden states ``[B, S, H]`` (after the last norm)
        and the head's matrix as ``chunked_lm_loss`` takes an embedding,
        ``[V, H]``; the token embedding is another."""
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="word_embeddings")
        head = self.param(
            "lm_head", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = wte(input_ids)
        with jax.named_scope("rotary_tables"):   # once a step
            cos, sin = rotary_tables(input_ids.shape[1], cfg.rotary_dim,
                                     cfg.rope_theta)
        layer = recomputed(Qwen3NextLayer, cfg.remat, self.remat_names)
        for i, kind in enumerate(cfg.layer_types):
            x = layer(cfg, kind, self.heads_sharding,
                      name=f"layer_{i}")(x, cos, sin)
        x = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype,
                               name="final_norm")(x)
        return x, head

    def __call__(self, input_ids):
        x, head = self.hidden_and_embedding(input_ids)
        return jnp.einsum("bsh,vh->bsv", x, head.astype(self.config.dtype),
                          preferred_element_type=jnp.float32)


expert_choices = functools.partial(layers.expert_choices,
                                   Qwen3NextLMHeadModel)


def remat_bytes(names, sequences: int, seq: int,
                config: Qwen3NextConfig) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it.  Tensor parallelism is not
    counted: the figure errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    layers = len(config.layer_types)
    linear, full = (config.layer_types.count(kind)
                    for kind in (LINEAR, FULL))
    value_heads, d_k, d_v = (config.linear_num_value_heads,
                             config.linear_key_head_dim,
                             config.linear_value_head_dim)
    rows = moe.dispatch_rows(1, config.num_experts_per_tok,
                             config.experts_held)   # of one token
    chunks, length = gated_delta.chunks_of(seq, config.linear_chunk_size)
    per_token = {
        "flash_out": full * config.num_attention_heads * config.head_dim
        * itemsize,
        "flash_lse": full * config.num_attention_heads * 4,
        moe.CHOICE_NAME: layers * config.num_experts_per_tok * 4,
        "gate_up": layers * 2 * config.shared_expert_intermediate_size
        * itemsize,
        # w in the compute dtype, u in float32
        gated_delta.WY_NAME: linear * value_heads * (d_k * itemsize
                                                     + d_v * 4),
        "in_proj": linear * config.linear_in_proj_dim * itemsize,
        moe.EXPERT_GATE_UP_NAME: layers * rows * 2
        * config.moe_intermediate_size * itemsize,
        moe.ROWS_NAME: layers * rows * config.hidden_size * itemsize}
    # v_new of every position and the state a chunk is entered with,
    # float32 both (padded positions counted)
    states = linear * sequences * chunks * value_heads * 4 * (
        length * d_v + d_k * d_v)
    return sum(states if name == gated_delta.STATES_NAME
               else sequences * seq * per_token[name] for name in names)
