"""GPT-style decoder-only language model family in Flax, bfloat16-first.

Completes the model zoo's transformer coverage next to the BERT
encoder family (the reference wraps user models and ships none of its
own; this zoo is what the framework's benchmarks, Adasum runs and
sharded-training paths exercise — SURVEY §2 model-family rows).

TPU-first design mirrors bert.py: all matmuls in bfloat16 (fp32
params), static shapes, attention through the Pallas flash kernels
(``ops/pallas_attention.py``, forward and backward, no S x S array in
HBM) on a TPU and as batched einsums elsewhere or where attention
dropout is applied, pre-LayerNorm residual blocks, and parameter naming
matched by :func:`horovod_tpu.parallel.sharding.gpt_partition_rules` so
kernels map onto tensor-parallel mesh axes.

What a training step holds in HBM between its forward and its backward
pass is decided here.  With ``GPTConfig.remat`` a block is recomputed
in the backward pass but for the arrays it names (``REMAT_NAMES``): the
flash kernels' output and row statistics and the outputs of its five
matmuls, so that the recomputation is two LayerNorms, the GELU and the
residual adds; where the matmuls' outputs would not fit the device the
kernels' names alone stay (``REMAT_CANDIDATES``: the step builder asks
``layers.kept_across_remat`` when the step is traced).  And the step's
loss (``layers.chunked_lm_loss``) walks chunks of the sequence, so the
``[B, S, V]`` logits never exist.
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

from .layers import (FLASH_NAMES, _flash_causal, attention_impl, mesh_of,
                     recomputed)

# What a recomputed block may keep from its forward pass, by
# ``checkpoint_name``: what the flash kernels name and the outputs of
# the block's five matmuls but ``output``, which nothing of the block
# reads.  The width of each is in ``remat_bytes``.
MATMUL_NAMES = ("query", "key", "value", "attention_out", "intermediate")
REMAT_NAMES = FLASH_NAMES + MATMUL_NAMES
# All of them or the kernels' alone (``layers.kept_across_remat``): the
# matmuls' outputs are one width but ``intermediate``, and a block that
# kept a few of them would still run the others twice.
REMAT_CANDIDATES = (REMAT_NAMES, FLASH_NAMES)


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
    # Recompute every block in the backward pass but for what it names
    # (REMAT_NAMES): by default the kernels' output and the five
    # matmuls' outputs stay and the cheap rest is recomputed; where the
    # step builder finds (``REMAT_CANDIDATES``, from the shapes and the
    # device's memory) that the matmuls' outputs do not fit, the
    # kernels' output alone stays and the matmuls run twice.
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


class CausalSelfAttention(nn.Module):
    config: GPTConfig
    # How the step this model is traced in shards ``[B, S, H, D]``
    # (the step builder says; its mesh also tells the platform); None
    # where the model is applied directly.
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        dense = lambda name: nn.DenseGeneral(
            features=(cfg.num_heads, head_dim), axis=-1, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        q = checkpoint_name(dense("query")(x), "query")
        k = checkpoint_name(dense("key")(x), "key")
        v = checkpoint_name(dense("value")(x), "value")
        dropout_applied = cfg.dropout > 0.0 and not deterministic
        # The kernels apply no attention dropout; and ``init`` wants
        # the parameters' shapes and nothing of the attention, so no
        # kernel is traced and lowered for it.
        kernels_apply = not dropout_applied and not self.is_initializing()
        mesh = mesh_of(self.heads_sharding)
        if attention_impl(cfg, mesh, kernels_apply) == "flash":
            if dropout_applied:
                raise NotImplementedError(
                    "attention_impl='flash' does not apply attention "
                    "dropout; set dropout=0 or use 'einsum' (same "
                    "guard as the BERT family).")
            ctx = _flash_causal(q, k, v,
                                self.heads_sharding).astype(cfg.dtype)
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
        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                              dtype=cfg.dtype, param_dtype=jnp.float32,
                              name="out")(ctx)
        return checkpoint_name(out, "attention_out")


class GPTBlock(nn.Module):
    """Pre-LN residual block (GPT-2 layout)."""
    config: GPTConfig
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        norm = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        h = CausalSelfAttention(cfg, self.heads_sharding, name="attention")(
            norm("attention_norm")(x), deterministic)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = x + h
        m = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="intermediate")(
            norm("mlp_norm")(x))
        m = nn.gelu(checkpoint_name(m, "intermediate"), approximate=True)
        m = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="output")(m)
        m = nn.Dropout(cfg.dropout)(m, deterministic=deterministic)
        return x + m


class GPTLMHeadModel(nn.Module):
    """Decoder stack + tied-embedding LM head."""
    config: GPTConfig
    heads_sharding: Optional[NamedSharding] = None
    # What a recomputed block keeps (``config.remat``); the step
    # builder hands over what fits its shapes and its device.
    remat_names: Tuple[str, ...] = REMAT_NAMES

    @nn.compact
    def hidden_and_embedding(self, input_ids, deterministic: bool = True):
        """The decoder stack: the final hidden states ``[B, S, H]`` and
        the token embedding ``[V, H]`` that the head is tied to (what
        ``chunked_lm_loss`` takes, and ``__call__`` makes logits of)."""
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
        block = recomputed(GPTBlock, cfg.remat, self.remat_names,
                           static_argnums=(2,))
        for i in range(cfg.num_layers):
            x = block(cfg, self.heads_sharding, name=f"layer_{i}")(
                x, deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="final_norm")(x)
        return x, wte.embedding

    def __call__(self, input_ids, deterministic: bool = True):
        x, embedding = self.hidden_and_embedding(input_ids, deterministic)
        logits = jnp.einsum("bsh,vh->bsv", x,
                            embedding.astype(self.config.dtype))
        return logits.astype(jnp.float32)


def remat_bytes(names, sequences: int, seq: int, config: GPTConfig) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it: every name is one
    ``hidden_size`` wide in the compute dtype but ``intermediate`` and
    the kernels' fp32 statistics, one a head.  Tensor parallelism
    (which splits all but ``attention_out``) is not counted: the figure
    errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    widths = dict.fromkeys(REMAT_NAMES, config.hidden_size * itemsize)
    widths["intermediate"] = config.intermediate_size * itemsize
    widths["flash_lse"] = config.num_heads * 4
    return sequences * seq * config.num_layers * sum(
        widths[name] for name in names)


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
