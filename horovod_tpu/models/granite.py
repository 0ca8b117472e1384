"""Granite 4.0-H: a decoder whose layers are of two kinds chosen by a
list, Mamba-2 state-space mixers beside grouped-query attention, each
followed by a gated MLP (IBM's ``granitemoehybrid`` family,
ibm-granite/granite-4.0-h-micro; the state-space layer is Mamba-2, Dao
and Gu 2024, arXiv:2405.21060).  ``rms(x) = x / sqrt(mean(x^2) + eps)``.

* Embedding: ``h = E[ids] * embedding_multiplier``; ``E`` is tied to the
  head, whose logits are divided by ``logits_scaling``.
* Every layer: ``h += residual_multiplier * mixer(rms(h) * w1)``, then
  ``h += residual_multiplier * mlp(rms(h) * w2)``.
* ``mlp(x) = (silu(a) * b) @ W_out`` with ``[a, b] = x @ W_in`` (held as
  its two halves, ``gate`` and ``up``); no bias.
* Attention mixer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads, each serving a run of
  consecutive query heads; no bias, no positional encoding; causal
  ``softmax(q k^T * attention_multiplier) v``.
* Mamba-2 mixer, ``H`` heads of size ``P``, state ``N``, one group:
  ``[z, xBC, dt] = x @ W_in_proj``; ``xBC = silu(conv(xBC) + b)`` with a
  causal depthwise convolution over ``mamba_d_conv`` positions; ``[x, B,
  C] = xBC``; ``dt = softplus(dt + dt_bias)`` and ``a_t = exp(-exp(A_log)
  dt_t)`` per head; ``S_t = a_t S_{t-1} + dt_t x_t (outer) B_t``; ``y_t =
  S_t C_t + D x_t``; ``y = rms(y * silu(z)) * w_norm`` over all channels;
  ``y @ W_out_proj``.

TPU-first like ``gpt.py``: matmuls in ``dtype`` (bfloat16) from float32
parameters; the recurrence in its chunked form (``ops/ssd.py``) with
``dt``, the decays, their sums and the carried state in float32;
attention through the Pallas flash kernels on a TPU, with keys and
values repeated to the query heads (the kernels take equal head
counts), and as grouped einsums elsewhere; with ``remat`` a layer is
recomputed in the backward pass but for the kernels' output and what
the device has room for of the widest matmuls' outputs
(``REMAT_CANDIDATES``).
Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.granite_partition_rules`.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding

from ..ops.ssd import ssd_chunked
from .layers import (FLASH_NAMES, GatedMLP, RMSNorm, causal_depthwise_conv,
                     grouped_causal_attention, prefixes, recomputed)

MAMBA, ATTENTION = "mamba", "attention"
# What a recomputed layer may keep from its forward pass, by
# ``checkpoint_name``: what the flash kernels name, and the outputs of
# the two widest matmuls (the MLP's gate and up, the Mamba mixer's
# input projection), dearest to recompute first: 34.5 and 13.6 ms of a
# 441 ms step at 2 x 4096 on a v5e (PERF.md, PR 29).  As many of the
# latter as fit the device are kept (``layers.kept_across_remat``).
MATMUL_NAMES = ("gate_up", "in_proj")
REMAT_NAMES = FLASH_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(FLASH_NAMES))
# Mamba-2's published initialisation of the step size: log-uniform in
# [DT_MIN, DT_MAX], never under DT_FLOOR; and of the decay rate:
# uniform in A_RANGE.
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192        # shared_intermediate_size
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Recompute every layer in the backward pass but for what it names
    # (REMAT_NAMES); the step builder finds which of the matmuls'
    # outputs fit the device.
    remat: bool = False
    # As ``GPTConfig.attention_impl``.
    attention_impl: str = "auto"

    def __post_init__(self):
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}; a layer "
                             f"is '{MAMBA}' or '{ATTENTION}'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def granite_tiny_config(**kw) -> GraniteConfig:
    """Tiny hybrid for tests and dry runs: two Mamba layers around one
    attention layer, 4 query heads over 2 key-value heads."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    layer_types=(MAMBA, ATTENTION, MAMBA),
                    num_attention_heads=4, num_key_value_heads=2,
                    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                    mamba_chunk_size=16)
    defaults.update(kw)
    return GraniteConfig(**defaults)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step size drawn log-uniformly."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


class Mamba2Mixer(nn.Module):
    config: GraniteConfig
    # How the step this model is traced in shards ``[B, S, heads, D]``;
    # None where the model is applied directly.
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, head_dim = cfg.mamba_n_heads, cfg.mamba_d_head
        inner, state = cfg.mamba_d_inner, cfg.mamba_d_state
        conv_width = inner + 2 * state
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        vector = lambda name, init: self.param(name, init, (heads,),
                                               jnp.float32)

        zxbcdt = checkpoint_name(
            dense(inner + conv_width + heads, "in_proj")(x), "in_proj")
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_width], axis=-1)
        with jax.named_scope("conv"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (cfg.mamba_d_conv, conv_width), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (conv_width,), jnp.float32)
            xbc = nn.silu(causal_depthwise_conv(xbc, kernel, bias))
            xs, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
            xs = xs.reshape(*xs.shape[:2], heads, head_dim)
            if self.heads_sharding is not None:
                xs = jax.lax.with_sharding_constraint(xs, self.heads_sharding)
        dt_bias = vector("dt_bias", _dt_bias_init)
        a_log = vector("A_log", _a_log_init)
        d = vector("D", nn.initializers.ones)
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_chunked(xs, dt, -jnp.exp(a_log), b, c,
                            cfg.mamba_chunk_size)
            y = y + xs * d.astype(cfg.dtype)[:, None]
        with jax.named_scope("gated_norm"):
            y = y.reshape(*y.shape[:2], inner) * nn.silu(z)
            y = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(y)
        return dense(cfg.hidden_size, "out_proj")(y)


class GroupedQueryAttention(nn.Module):
    config: GraniteConfig
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        dense = lambda heads, name: nn.DenseGeneral(
            features=(heads, cfg.head_dim), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        q = dense(q_heads, "query")(x)
        k = dense(kv_heads, "key")(x)
        v = dense(kv_heads, "value")(x)
        ctx = grouped_causal_attention(
            q, k, v, cfg.attention_multiplier, cfg, self.heads_sharding,
            self.is_initializing())
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


class GraniteLayer(nn.Module):
    config: GraniteConfig
    kind: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        mixer = (Mamba2Mixer if self.kind == MAMBA
                 else GroupedQueryAttention)(cfg, self.heads_sharding,
                                             name=self.kind)
        x = x + cfg.residual_multiplier * mixer(norm("mixer_norm")(x))
        m = GatedMLP(cfg.intermediate_size, cfg.dtype, name="mlp")(
            norm("mlp_norm")(x))
        return x + cfg.residual_multiplier * m


class GraniteLMHeadModel(nn.Module):
    """The hybrid stack and the tied, scaled head."""
    config: GraniteConfig
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
        x = wte(input_ids) * cfg.embedding_multiplier
        layer = recomputed(GraniteLayer, cfg.remat, self.remat_names)
        for i, kind in enumerate(cfg.layer_types):
            x = layer(cfg, kind, self.heads_sharding, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return x, wte.embedding

    def __call__(self, input_ids):
        x, embedding = self.hidden_and_embedding(input_ids)
        logits = jnp.einsum("bsh,vh->bsv", x,
                            embedding.astype(self.config.dtype),
                            preferred_element_type=jnp.float32)
        return logits / self.config.logits_scaling


def remat_bytes(names, sequences: int, seq: int,
                config: GraniteConfig) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it: the kernels' output and
    fp32 row statistics in every attention layer, gate and up in every
    layer, the input projection's output in every Mamba layer.  Tensor
    parallelism (which splits them all) is not counted: the figure
    errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    kinds = config.layer_types
    per_token = {
        "flash_out": kinds.count(ATTENTION) * config.hidden_size * itemsize,
        "flash_lse": kinds.count(ATTENTION) * config.num_attention_heads * 4,
        "gate_up": len(kinds) * 2 * config.intermediate_size * itemsize,
        "in_proj": kinds.count(MAMBA) * itemsize * (
            2 * config.mamba_d_inner + 2 * config.mamba_d_state
            + config.mamba_n_heads)}
    return sequences * seq * sum(per_token[name] for name in names)
