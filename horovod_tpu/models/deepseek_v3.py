"""DeepSeek-V3-style decoder (``model_type`` ``deepseek_v3``, as
kakaocorp/kanana-2-30b-a3b publishes it): multi-head latent attention,
and after the leading dense layers a sparse feed-forward that is top-k
routed experts plus an always-on shared expert.  ``rms(x) = x /
sqrt(mean(x^2) + eps)``; no bias in any projection.

* Embedding ``h = E[ids]``; the head is a matrix of its own: ``logits =
  (rms(h) * w_final) @ W_head``.
* Every layer: ``h += attn(rms(h) * w_1)``, then ``h += ffn(rms(h) *
  w_2)``; ``ffn`` dense in the first ``first_k_dense_replace`` layers,
  sparse in the rest.
* ``attn`` (no low-rank query path): ``q = u @ Wq`` as heads of
  ``qk_head_dim = [q_nope | q_rope]``; ``u @ W_kva = [c_kv
  (kv_lora_rank) | k_rope (qk_rope_head_dim)]``; ``[k_nope | v]`` of
  every head ``= (rms(c_kv) * w_kv) @ W_kvb``.  Rotary positions on
  ``q_rope`` of every head and on the ONE ``k_rope``, channels paired
  ``(2i, 2i + 1)``; head ``j``'s key is ``[k_nope_j | rot(k_rope)]``.
  Causal ``softmax(q k^T / sqrt(qk_head_dim)) v`` with values
  ``v_head_dim`` wide.
* Dense ``ffn``: ``(silu(x @ W1) * (x @ W3)) @ W2``.
* Sparse ``ffn`` (``parallel/moe.py`` ``routed_experts``): sigmoid
  scores over ``n_routed_experts``, the top ``num_experts_per_tok`` of
  score plus a selection bias chosen, their gates divided by their sum
  plus 1e-20 and multiplied by ``routed_scaling_factor``; plus ONE
  SwiGLU of width ``n_shared_experts x moe_intermediate_size`` on every
  token, with no gate.  This model holds ``experts_held`` of the routed
  experts from ``first_expert`` on and computes their part of the
  routed sum; the shared expert is whole on every chip.

TPU-first like ``lfm2.py``: matmuls in ``dtype`` (bfloat16) from float32
parameters; the router, the norms and the rotation in float32.  The
rotation is computed de-interleaved: the even channels of a rotary part
then the odd ones, rotated as halves, on queries and keys alike, which
permutes both the same way and leaves every score what the pairing
``(2i, 2i + 1)`` gives (a slice of every other lane once, not a pair of
two in the minor dimension).  On a TPU attention is the Pallas flash
kernels at two widths, for which keys and values are expanded for every
head, the one rotated key laid out once a head (``hvd_mla_expand_bytes``
says what that writes); elsewhere it is einsums, which read the one
rotated key as it is.  In training nothing caches the latent.  With
``remat`` a layer is recomputed in the backward pass but for the flash
kernels' output, the routers' choice and what the device has room for
(``REMAT_CANDIDATES``).  Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.deepseek_v3_partition_rules`.
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
from .layers import (FLASH_NAMES, GatedMLP, RMSNorm, SparseFFN, _flash_causal,
                     attention_impl, mesh_of, prefixes, recomputed,
                     rotary_tables)
from .layers import given_choices  # noqa: F401  (the benchmark's name)

DENSE, SPARSE = "dense", "sparse"
# The class's own addend to the sum of the chosen gates.
GATE_SUM_EPS = 1e-20
# The expanded keys and values, by ``checkpoint_name``: recomputing
# them is the up-projection, the rotation and the layout over again.
EXPANDED_KV_NAME = "mla_kv"
# What a recomputed layer may keep from its forward pass beside what the
# flash kernels name, dearest to recompute a byte first: gate and up of
# the dense SwiGLU and of the shared expert, the expanded keys and
# values, the routed experts' gate and up (two grouped products over a
# buffer of which an eighth holds a pair), the sorted rows (a gather).
# As many as fit the device are kept (``layers.kept_across_remat``; on
# the v5e at 2 x 8192 the first two fit, and the step that keeps the
# expanded keys and values is also a quarter of the compiled size of
# one that makes them again: PERF.md, PR 37).
MATMUL_NAMES = ("gate_up", EXPANDED_KV_NAME, moe.EXPERT_GATE_UP_NAME,
                moe.ROWS_NAME)
# Always kept: the kernels' output, and the routers' choice, which a
# recomputed pass must not make again (``parallel/moe.py``).
KEPT_NAMES = FLASH_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144        # the dense SwiGLU's width
    moe_intermediate_size: int = 768     # a routed expert's
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1       # the leading dense layers
    num_attention_heads: int = 32
    kv_lora_rank: int = 512              # the latent's width
    qk_head_dim: int = 192               # [nope | rope] of queries and keys
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The router's width, and the run of routed experts this model holds.
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    experts_held: int = 128
    first_expert: int = 0
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # As ``GraniteConfig``'s.
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if not 0 < self.qk_rope_head_dim <= self.qk_head_dim:
            raise ValueError("the rotary part, qk_rope_head_dim, is a part "
                             "of a query's qk_head_dim channels")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions turn pairs of channels")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError("the experts held, first_expert to first_expert "
                             "+ experts_held - 1, lie among n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts layers of "
                             "num_hidden_layers")

    @property
    def qk_nope_head_dim(self) -> int:
        return self.qk_head_dim - self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def ffn_types(self) -> Tuple[str, ...]:
        dense = self.first_k_dense_replace
        return (DENSE,) * dense + (SPARSE,) * (self.num_hidden_layers - dense)


def deepseek_v3_tiny_config(**kw) -> DeepseekV3Config:
    """Tiny stack for tests and dry runs: one dense layer and two sparse
    ones; 4 heads of 24 = 16 + 8 for queries and keys and 16 for values
    over a latent of 32; 8 routed experts of which 4 are held, top 2, a
    routed scale other than 1, one shared expert's width twice."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, kv_lora_rank=32, qk_head_dim=24,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    num_experts_per_tok=2, experts_held=4)
    defaults.update(kw)
    return DeepseekV3Config(**defaults)


def rotate_pairs(x, cos, sin):
    """Rotary positions over the pairing ``(2i, 2i + 1)``, written
    de-interleaved: ``[even channels | odd channels]``, channel ``2i``
    turned with ``2i + 1`` by ``cos`` and ``sin`` ``[S, d / 2]``.  The
    same permutation on queries and on keys, so their products are
    those of the interleaved form.  ``x``: ``[B, S, heads, d]``;
    float32 inside, ``x``'s type out."""
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], -1).astype(x.dtype)


def expand_bytes(tokens: int, config: DeepseekV3Config, heads: int) -> int:
    """Bytes one layer's forward writes on one device for the expanded
    keys and values of ``tokens`` tokens and ``heads`` heads: every
    head's key ``[k_nope | rot(k_rope)]``, the rotated part the same in
    all of them, and every head's value.  A kernel that read the latent,
    or the one rotated key, would not write them."""
    return tokens * heads * (config.qk_head_dim + config.v_head_dim) \
        * np.dtype(config.dtype).itemsize


class LatentAttention(nn.Module):
    """Multi-head latent attention: keys and values through one narrow
    down-projection, a rotary part that every head's key shares."""
    config: DeepseekV3Config
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        per_head = lambda width, name: nn.DenseGeneral(
            features=(heads, width), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        q = per_head(cfg.qk_head_dim, "query")(x)
        latent = nn.Dense(cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                          use_bias=False, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="kv_down")(x)
        c_kv, k_rope = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
        c_kv = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_norm")(c_kv)
        k_nope, v = jnp.split(
            per_head(nope + cfg.v_head_dim, "kv_up")(c_kv), [nope], axis=-1)
        q_nope, q_rope = jnp.split(q, [nope], axis=-1)
        with jax.named_scope("rotary"):
            q_rope = rotate_pairs(q_rope, cos, sin)
            k_rope = rotate_pairs(k_rope[:, :, None, :], cos, sin)
        scale = cfg.qk_head_dim ** -0.5
        mesh = mesh_of(self.heads_sharding)
        if attention_impl(cfg, mesh, not self.is_initializing()) == "flash":
            with jax.named_scope("rotary"):
                # The kernels take a key a head: the one rotated key is
                # laid out once for every head, behind its own part.
                q = jnp.concatenate([q_nope, q_rope], -1)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(
                        k_rope, k_nope.shape[:-1] + k_rope.shape[-1:])], -1)
            k = checkpoint_name(k, EXPANDED_KV_NAME)
            v = checkpoint_name(v, EXPANDED_KV_NAME)
            ctx = _flash_causal(q, k, v, self.heads_sharding, scale=scale)
            ctx = ctx.astype(cfg.dtype)
        else:
            seq = x.shape[1]
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                      + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
            causal = jnp.tril(jnp.ones((seq, seq), bool))
            scores = jnp.where(causal, scores * scale,
                               jnp.finfo(cfg.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype), v)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


def sparse_ffn(config: DeepseekV3Config, mesh) -> SparseFFN:
    """The routed experts of a sparse layer (sigmoid scores plus a
    selection bias) and beside them the shared expert, with no gate."""
    return SparseFFN(
        experts=config.n_routed_experts, held=config.experts_held,
        first_expert=config.first_expert, top_k=config.num_experts_per_tok,
        width=config.moe_intermediate_size, normalize=config.norm_topk_prob,
        dtype=config.dtype, scale=config.routed_scaling_factor,
        gate_sum_eps=GATE_SUM_EPS, shared=config.shared_width, mesh=mesh,
        name="moe")


class DeepseekV3Layer(nn.Module):
    config: DeepseekV3Config
    ffn: str
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + LatentAttention(cfg, self.heads_sharding, name="attention")(
            norm("attention_norm")(x), cos, sin)
        u = norm("ffn_norm")(x)
        if self.ffn == DENSE:
            return x + GatedMLP(cfg.intermediate_size, cfg.dtype,
                                name="mlp")(u)
        return x + sparse_ffn(cfg, mesh_of(self.heads_sharding))(u)


class DeepseekV3LMHeadModel(nn.Module):
    """The stack and its untied head."""
    config: DeepseekV3Config
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
            cos, sin = rotary_tables(input_ids.shape[1],
                                     cfg.qk_rope_head_dim, cfg.rope_theta)
        layer = recomputed(DeepseekV3Layer, cfg.remat, self.remat_names)
        for i, ffn in enumerate(cfg.ffn_types):
            x = layer(cfg, ffn, self.heads_sharding,
                      name=f"layer_{i}")(x, cos, sin)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return x, head

    def __call__(self, input_ids):
        x, head = self.hidden_and_embedding(input_ids)
        return jnp.einsum("bsh,vh->bsv", x, head.astype(self.config.dtype),
                          preferred_element_type=jnp.float32)


expert_choices = functools.partial(layers.expert_choices,
                                   DeepseekV3LMHeadModel)


def remat_bytes(names, sequences: int, seq: int,
                config: DeepseekV3Config) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it.  Tensor parallelism is not
    counted: the figure errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    layers, heads = config.num_hidden_layers, config.num_attention_heads
    dense, sparse = (config.ffn_types.count(kind)
                     for kind in (DENSE, SPARSE))
    rows = moe.dispatch_rows(1, config.num_experts_per_tok,
                             config.experts_held)   # of one token
    per_token = {
        "flash_out": layers * heads * config.v_head_dim * itemsize,
        "flash_lse": layers * heads * 4,
        moe.CHOICE_NAME: sparse * config.num_experts_per_tok * 4,
        "gate_up": 2 * itemsize * (dense * config.intermediate_size
                                   + sparse * config.shared_width),
        moe.EXPERT_GATE_UP_NAME: sparse * rows * 2
        * config.moe_intermediate_size * itemsize,
        EXPANDED_KV_NAME: layers * expand_bytes(1, config, heads),
        moe.ROWS_NAME: sparse * rows * config.hidden_size * itemsize}
    return sequences * seq * sum(per_token[name] for name in names)
