"""AFMoE decoder (``model_type`` ``afmoe``, as arcee-ai/Trinity-Mini
publishes it): grouped-query attention of two kinds in one stack, by
``layer_types``, each head gated, and after the leading dense layers a
sparse feed-forward whose selection bias the step moves.  ``rms(x) = x /
sqrt(mean(x^2) + eps)``; no bias in any projection.

* Embedding ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``); the
  head is a matrix of its own: ``logits = (rms(h) * w_final) @ W_head``.
* Every layer, four norms: ``a = h + rms_2(attn(rms_1(h)))``, then ``h =
  a + rms_4(ffn(rms_3(a)))``, each ``rms_i`` with its own weight.
* ``attn(u)``: ``q = u W_q`` as ``num_attention_heads`` heads, ``[k | v]
  = u W_kv`` as ``num_key_value_heads`` heads, ``g = u W_g`` a gate a
  channel of every query head; ``q = rms(q) * w_q`` and ``k = rms(k) *
  w_k`` over each head's own width.  On a ``sliding_attention`` layer
  queries and keys are rotated over the whole head in the rotate-half
  pairing, and query ``i`` sees the keys ``j`` with ``0 <= i - j <
  sliding_window``; on a ``full_attention`` layer NOTHING tells a
  position from another but the causal mask, ``0 <= i - j``.  Each
  key-value head serves a run of consecutive query heads; ``o =
  softmax(q k^T / sqrt(d)) v * sigmoid(g)``; ``o W_o``.
* ``ffn``, the first ``num_dense_layers`` layers: ``(silu(x W1) * (x
  W3)) W2``.  The rest (``parallel/moe.py`` ``routed_experts``): sigmoid
  scores over ``num_experts``, the top ``num_experts_per_tok`` of score
  plus the selection bias chosen, their gates divided by their sum plus
  1e-20 (``route_norm``) and multiplied by ``route_scale``; plus ONE
  SwiGLU of ``num_shared_experts x moe_intermediate_size`` on every
  token, with no gate.  This model holds ``experts_held`` of the routed
  experts from ``first_expert`` on.
* The selection bias is moved once a step, outside the gradient and the
  optimizer, from the step's own choices (``moe.moved_bias`` with
  ``load_balance_coeff``; ``training.py`` applies it, the family's row
  says so).

TPU-first like ``lfm2.py``: matmuls in ``dtype`` (bfloat16) from float32
parameters; the router, the norms and the rotation in float32.  On a TPU
both kinds of attention are the Pallas flash kernels, the window layers'
a walk of the band alone (``ops/pallas_attention.py``); elsewhere
einsums under ``layers.visible_keys``.  With ``remat`` a layer is
recomputed in the backward pass but for the flash kernels' output, the
routers' choice and what the device has room for
(``REMAT_CANDIDATES``).  Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.afmoe_partition_rules`.
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
                     grouped_causal_attention, mesh_of, prefixes, recomputed,
                     rotary_tables, rotate)

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# The class's own addend to the sum of the chosen gates.
GATE_SUM_EPS = 1e-20
# The three projections attention reads its input through (query, keys
# and values, the gate), by ``checkpoint_name``.
ATTENTION_IN_NAME = "attention_in"
# What a recomputed layer may keep from its forward pass beside what the
# flash kernels name, dearest to recompute a byte first: gate and up of
# the dense SwiGLU and of the shared expert, attention's three input
# projections, the routed experts' gate and up (two grouped products
# over a buffer of which an eighth holds a pair), the sorted rows (a
# gather).  As many as fit the device are kept
# (``layers.kept_across_remat``).
MATMUL_NAMES = ("gate_up", ATTENTION_IN_NAME, moe.EXPERT_GATE_UP_NAME,
                moe.ROWS_NAME)
# Always kept: the kernels' output, and the routers' choice, which a
# recomputed pass must not make again (``parallel/moe.py``).
KEPT_NAMES = FLASH_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144        # the dense SwiGLU's width
    moe_intermediate_size: int = 1024    # a routed expert's
    num_hidden_layers: int = 32
    num_dense_layers: int = 2            # the leading dense layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # One kind of attention a layer; None: every fourth layer, from the
    # fourth, full, the rest a window (the published stack).
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    # The router's width, and the run of routed experts this model holds.
    num_experts: int = 128
    num_experts_per_tok: int = 8
    experts_held: int = 128
    first_expert: int = 0
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    # The step of the rule that moves the selection bias; 0: no rule.
    load_balance_coeff: float = 1e-3
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # As ``GraniteConfig``'s.
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % 4 == 0 else SLIDING
                for i in range(self.num_hidden_layers)))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(f"layer_types gives each of num_hidden_layers "
                             f"'{SLIDING}' or '{FULL}'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError("the experts held, first_expert to first_expert "
                             "+ experts_held - 1, lie among num_experts")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers counts layers of "
                             "num_hidden_layers")
        if self.head_dim % 2:
            raise ValueError("rotary positions pair a head's halves")
        if self.sliding_window < 1:
            raise ValueError("a window holds the query's own position at "
                             "least")

    @property
    def shared_width(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    @property
    def ffn_types(self) -> Tuple[str, ...]:
        dense = self.num_dense_layers
        return (DENSE,) * dense + (SPARSE,) * (self.num_hidden_layers - dense)


def afmoe_tiny_config(**kw) -> AfmoeConfig:
    """Tiny stack for tests and dry runs: a dense window layer, then one
    period (three window layers and a full one) of sparse layers; 4
    query heads over 2 key-value heads of 16, a window of 8; 8 routed
    experts of which 4 are held, top 2, a routed scale other than 1."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_hidden_layers=5,
                    num_dense_layers=1,
                    layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, sliding_window=8, num_experts=8,
                    num_experts_per_tok=2, experts_held=4)
    defaults.update(kw)
    return AfmoeConfig(**defaults)


class GatedAttention(nn.Module):
    """Grouped-query attention, queries and keys normed over their
    head, every channel of a head's output behind a sigmoid gate of its
    own; a window and rotary positions, or neither."""
    config: AfmoeConfig
    kind: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        dense = lambda heads, width, name: nn.DenseGeneral(
            features=(heads, width), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        q = checkpoint_name(dense(q_heads, head_dim, "query")(x),
                            ATTENTION_IN_NAME)
        # A head's key and its value, side by side WITHIN the head.
        k, v = jnp.split(checkpoint_name(
            dense(kv_heads, 2 * head_dim, "key_value")(x),
            ATTENTION_IN_NAME), 2, axis=-1)
        gate = checkpoint_name(dense(q_heads, head_dim, "gate")(x),
                               ATTENTION_IN_NAME)
        with jax.named_scope("qk_norm"):
            q, k = norm("query_norm")(q), norm("key_norm")(k)
        window = None
        if self.kind == SLIDING:
            window = cfg.sliding_window
            with jax.named_scope("rotary"):
                q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        ctx = grouped_causal_attention(
            q, k, v, head_dim ** -0.5, cfg, self.heads_sharding,
            self.is_initializing(), window=window)
        with jax.named_scope("gate"):
            ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                cfg.dtype)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


def sparse_ffn(config: AfmoeConfig, mesh) -> SparseFFN:
    """The routed experts of a sparse layer (sigmoid scores plus the
    selection bias) and beside them the shared expert, with no gate."""
    return SparseFFN(
        experts=config.num_experts, held=config.experts_held,
        first_expert=config.first_expert, top_k=config.num_experts_per_tok,
        width=config.moe_intermediate_size, normalize=config.route_norm,
        dtype=config.dtype, scale=config.route_scale,
        gate_sum_eps=GATE_SUM_EPS, shared=config.shared_width, mesh=mesh,
        name="moe")


class AfmoeLayer(nn.Module):
    config: AfmoeConfig
    kind: str
    ffn: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + norm("post_attention_norm")(GatedAttention(
            cfg, self.kind, self.heads_sharding, name="attention")(
                norm("input_norm")(x), cos, sin))
        u = norm("pre_mlp_norm")(x)
        if self.ffn == DENSE:
            y = GatedMLP(cfg.intermediate_size, cfg.dtype, name="mlp")(u)
        else:
            y = sparse_ffn(cfg, mesh_of(self.heads_sharding))(u)
        return x + norm("post_mlp_norm")(y)


class AfmoeLMHeadModel(nn.Module):
    """The stack and its untied head."""
    config: AfmoeConfig
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
        if cfg.mup_enabled:
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
        with jax.named_scope("rotary_tables"):   # once a step
            cos, sin = rotary_tables(input_ids.shape[1], cfg.head_dim,
                                     cfg.rope_theta)
        layer = recomputed(AfmoeLayer, cfg.remat, self.remat_names)
        for i, (kind, ffn) in enumerate(zip(cfg.layer_types,
                                            cfg.ffn_types)):
            x = layer(cfg, kind, ffn, self.heads_sharding,
                      name=f"layer_{i}")(x, cos, sin)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return x, head

    def __call__(self, input_ids):
        x, head = self.hidden_and_embedding(input_ids)
        return jnp.einsum("bsh,vh->bsv", x, head.astype(self.config.dtype),
                          preferred_element_type=jnp.float32)


expert_choices = functools.partial(layers.expert_choices, AfmoeLMHeadModel)


def remat_bytes(names, sequences: int, seq: int, config: AfmoeConfig) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it.  Tensor parallelism is not
    counted: the figure errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    depth, heads = config.num_hidden_layers, config.num_attention_heads
    dense, sparse = (config.ffn_types.count(kind)
                     for kind in (DENSE, SPARSE))
    rows = moe.dispatch_rows(1, config.num_experts_per_tok,
                             config.experts_held)   # of one token
    per_token = {
        "flash_out": depth * heads * config.head_dim * itemsize,
        "flash_lse": depth * heads * 4,
        moe.CHOICE_NAME: sparse * config.num_experts_per_tok * 4,
        "gate_up": 2 * itemsize * (dense * config.intermediate_size
                                   + sparse * config.shared_width),
        ATTENTION_IN_NAME: depth * 2 * itemsize * config.head_dim
        * (heads + config.num_key_value_heads),
        moe.EXPERT_GATE_UP_NAME: sparse * rows * 2
        * config.moe_intermediate_size * itemsize,
        moe.ROWS_NAME: sparse * rows * config.hidden_size * itemsize}
    return sequences * seq * sum(per_token[name] for name in names)
