"""GPT-style decoder-only language model family in Flax, bfloat16-first.

Completes the model zoo's transformer coverage next to the BERT
encoder family (the reference wraps user models and ships none of its
own; this zoo is what the framework's benchmarks, Adasum runs and
sharded-training paths exercise — SURVEY §2 model-family rows).

TPU-first design mirrors bert.py: all matmuls in bfloat16 (fp32
params), static shapes, attention through the Pallas flash kernels
(``ops/pallas_attention.py``, forward and backward, no S x S array in
HBM) on a TPU and as batched einsums elsewhere or where attention
dropout is applied, pre-LayerNorm residual blocks, optional per-layer
``jax.checkpoint`` rematerialisation, and parameter naming matched by
:func:`horovod_tpu.parallel.sharding.gpt_partition_rules` so kernels
map onto tensor-parallel mesh axes.
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # "auto": the Pallas kernels (ops/pallas_attention.py) on a TPU
    # wherever no attention dropout is applied, plain XLA einsums
    # elsewhere.  "einsum" and "flash" name one path; the CPU tests
    # name "flash" to run the kernels in interpret mode.
    attention_impl: str = "auto"


def gpt2_small_config(**kw) -> GPTConfig:
    return GPTConfig(**kw)


def gpt2_medium_config(**kw) -> GPTConfig:
    defaults = dict(hidden_size=1024, num_layers=24, num_heads=16,
                    intermediate_size=4096)
    defaults.update(kw)
    return GPTConfig(**defaults)


def gpt_tiny_config(**kw) -> GPTConfig:
    """Tiny config for tests and multi-chip dry runs."""
    defaults = dict(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128,
                    max_position_embeddings=128, dropout=0.0)
    defaults.update(kw)
    return GPTConfig(**defaults)


def _flash_causal(q, k, v, sharding: Optional[NamedSharding]):
    from ..ops.pallas_attention import flash_attention
    attend = functools.partial(flash_attention, causal=True)
    if sharding is not None and sharding.mesh.size > 1:
        # GSPMD does not partition a Mosaic kernel; attention is
        # independent per sequence and per head, so each chip runs
        # the kernels on the shard the step builder gives it.
        attend = jax.shard_map(
            attend, mesh=sharding.mesh, in_specs=(sharding.spec,) * 3,
            out_specs=sharding.spec, check_vma=False)
    return attend(q, k, v)


def attention_impl(config: GPTConfig, mesh, kernels_apply: bool) -> str:
    """``config.attention_impl`` with "auto" resolved: the kernels on a
    TPU (the mesh's platform, or the default backend where there is no
    mesh) wherever they apply, the einsums elsewhere."""
    if config.attention_impl != "auto":
        return config.attention_impl
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    return "flash" if platform == "tpu" and kernels_apply else "einsum"


class CausalSelfAttention(nn.Module):
    config: GPTConfig
    # How the step this model is traced in shards ``[B, S, H, D]``
    # (the step builder says; its mesh also tells the platform); None
    # where the model is applied directly.
    qkv_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        dense = lambda name: nn.DenseGeneral(
            features=(cfg.num_heads, head_dim), axis=-1, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        q = dense("query")(x)
        k = dense("key")(x)
        v = dense("value")(x)
        dropout_applied = cfg.dropout > 0.0 and not deterministic
        # The kernels apply no attention dropout; and ``init`` wants
        # the parameters' shapes and nothing of the attention, so no
        # kernel is traced and lowered for it.
        kernels_apply = not dropout_applied and not self.is_initializing()
        mesh = (None if self.qkv_sharding is None
                else self.qkv_sharding.mesh)
        if attention_impl(cfg, mesh, kernels_apply) == "flash":
            if dropout_applied:
                raise NotImplementedError(
                    "attention_impl='flash' does not apply attention "
                    "dropout; set dropout=0 or use 'einsum' (same "
                    "guard as the BERT family).")
            ctx = _flash_causal(q, k, v, self.qkv_sharding).astype(cfg.dtype)
        else:
            seq = x.shape[1]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            scores = scores / math.sqrt(head_dim)
            causal = jnp.tril(jnp.ones((seq, seq), bool))
            scores = jnp.where(causal[None, None],
                               scores, jnp.finfo(cfg.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            probs = probs.astype(cfg.dtype)
            probs = nn.Dropout(cfg.dropout)(probs,
                                            deterministic=deterministic)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               name="out")(ctx)


class GPTBlock(nn.Module):
    """Pre-LN residual block (GPT-2 layout)."""
    config: GPTConfig
    qkv_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        norm = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        h = CausalSelfAttention(cfg, self.qkv_sharding, name="attention")(
            norm("attention_norm")(x), deterministic)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = x + h
        m = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="intermediate")(
            norm("mlp_norm")(x))
        m = nn.gelu(m, approximate=True)
        m = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="output")(m)
        m = nn.Dropout(cfg.dropout)(m, deterministic=deterministic)
        return x + m


class GPTLMHeadModel(nn.Module):
    """Decoder stack + tied-embedding LM head."""
    config: GPTConfig
    qkv_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        seq = input_ids.shape[1]
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=jnp.float32,
                       name="word_embeddings")
        wpe = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=jnp.float32,
                       name="position_embeddings")
        x = wte(input_ids) + wpe(jnp.arange(seq)[None, :])
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        block = GPTBlock
        if cfg.remat:
            # A block is recomputed in the backward pass but for what
            # the flash kernels name (their output and its row
            # statistics, which their backward reads): recomputing
            # those would be the forward kernel over again.
            block = nn.remat(
                GPTBlock, static_argnums=(2,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"))
        for i in range(cfg.num_layers):
            x = block(cfg, self.qkv_sharding, name=f"layer_{i}")(
                x, deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="final_norm")(x)
        logits = jnp.einsum("bsh,vh->bsv", x,
                            wte.embedding.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def lm_loss(logits, input_ids, mask=None):
    """Next-token cross-entropy: position t predicts token t+1.
    ``mask`` (optional) is 1 where the TARGET token counts."""
    logits = logits[:, :-1]
    targets = input_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -ll.mean()
    m = mask[:, 1:].astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
