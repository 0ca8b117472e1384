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
residual adds; :func:`remat_names` drops the matmuls' outputs where
they would not fit the device (``make_gpt_train_step`` asks it when the
step is traced).  And the step's loss (:func:`chunked_lm_loss`) walks
chunks of the sequence, so the ``[B, S, V]`` logits never exist.
"""

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding

# What a recomputed block may keep from its forward pass, by
# ``checkpoint_name``: what the flash kernels name (recomputing those
# is the forward kernel over again) and the outputs of the block's
# five matmuls but ``output``, which nothing of the block reads.  The
# width of each is in ``remat_bytes``.
FLASH_NAMES = ("flash_out", "flash_lse")
MATMUL_NAMES = ("query", "key", "value", "attention_out", "intermediate")
REMAT_NAMES = FLASH_NAMES + MATMUL_NAMES
# Tokens one device holds in one chunk of ``chunked_lm_loss``.  2048 is
# 16 sequences x 128 positions, chosen on the v5e at 16 x 1024 (PERF.md
# PR 26): 2048 x 50257 fp32 logits are 0.41 GB where all 1024 positions
# were 3.29, and a chunk's products still feed the MXU.  Counted in
# tokens and not in positions since PR 35: every chunk reads and writes
# the whole fp32 gradient of the embedding, so two sequences of 4096
# walk 4 chunks of 1024 positions, not 32 of 128.
LOSS_CHUNK_TOKENS = 2048


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
    # step builder finds (``remat_names``, from the shapes and the
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


def _flash_causal(q, k, v, sharding: Optional[NamedSharding],
                  scale: Optional[float] = None):
    from ..ops.pallas_attention import flash_attention
    attend = functools.partial(flash_attention, causal=True, scale=scale)
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
        q = checkpoint_name(dense("query")(x), "query")
        k = checkpoint_name(dense("key")(x), "key")
        v = checkpoint_name(dense("value")(x), "value")
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
        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                              dtype=cfg.dtype, param_dtype=jnp.float32,
                              name="out")(ctx)
        return checkpoint_name(out, "attention_out")


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
        m = nn.gelu(checkpoint_name(m, "intermediate"), approximate=True)
        m = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="output")(m)
        m = nn.Dropout(cfg.dropout)(m, deterministic=deterministic)
        return x + m


class GPTLMHeadModel(nn.Module):
    """Decoder stack + tied-embedding LM head."""
    config: GPTConfig
    qkv_sharding: Optional[NamedSharding] = None
    # What a recomputed block keeps (``config.remat``); the step
    # builder hands over what ``remat_names`` chose for its shapes.
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
        block = GPTBlock
        if cfg.remat:
            block = nn.remat(
                GPTBlock, static_argnums=(2,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *self.remat_names))
        for i in range(cfg.num_layers):
            x = block(cfg, self.qkv_sharding, name=f"layer_{i}")(
                x, deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="final_norm")(x)
        return x, wte.embedding

    def __call__(self, input_ids, deterministic: bool = True):
        x, embedding = self.hidden_and_embedding(input_ids, deterministic)
        logits = jnp.einsum("bsh,vh->bsv", x,
                            embedding.astype(self.config.dtype))
        return logits.astype(jnp.float32)


def remat_bytes(names, tokens: int, hidden: int, intermediate: int,
                heads: int, layers: int, itemsize: int) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``tokens`` of the batch on it: every name is one ``hidden`` wide in
    the compute dtype but ``intermediate`` and the kernels' fp32
    statistics, one a head.  Tensor parallelism (which splits all but
    ``attention_out``) is not counted: the figure errs high."""
    widths = dict.fromkeys(REMAT_NAMES, hidden * itemsize)
    widths["intermediate"] = intermediate * itemsize
    widths["flash_lse"] = heads * 4
    return tokens * layers * sum(widths[name] for name in names)


def remat_names(tokens: int, hidden: int, intermediate: int, heads: int,
                layers: int, itemsize: int, state_bytes: int,
                memory_limit: Optional[int]) -> Tuple[str, ...]:
    """The names a recomputed block keeps: all of ``REMAT_NAMES`` where
    they fit one device's ``memory_limit`` bytes beside the state the
    step is handed (``state_bytes``: parameters and optimizer state on
    that device) and a margin of a quarter of the memory (the
    gradients, the layers' inputs, a chunk of the loss, the compiler's
    own temporaries: 1.9 GB of 16.9 in the benchmark's cell); the
    kernels' names alone where they do not, and every name where the
    device reports no limit (a CPU, a chip that is only described)."""
    if memory_limit is None:
        return REMAT_NAMES
    kept = remat_bytes(REMAT_NAMES, tokens, hidden, intermediate, heads,
                       layers, itemsize)
    fits = kept + state_bytes + memory_limit // 4 <= memory_limit
    return REMAT_NAMES if fits else FLASH_NAMES


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


def loss_chunks(seq: int, sequences: int) -> Tuple[int, int]:
    """``(count, length)`` of the chunks ``chunked_lm_loss`` walks over
    ``seq`` positions with ``sequences`` of the batch on one device:
    the fewest chunks of at most ``LOSS_CHUNK_TOKENS`` tokens a device
    (``sequences * length``), one chunk for a short batch, a whole
    position where the sequences alone are more than that, and as even
    as a length that the count does not divide allows."""
    longest = max(1, LOSS_CHUNK_TOKENS // sequences)
    count = -(-seq // longest)
    return count, -(-seq // count)


def _chunk_nll(h, table, targets, scale: float):
    """One chunk: fp32 logits ``[B, C, V]`` of ``h`` ``[B, C, H]``
    (products on ``h``'s dtype, accumulated in fp32) times ``scale``,
    each position's log-sum-exp and its negative log-likelihood of
    ``targets``."""
    logits = jnp.einsum("bch,vh->bcv", h, table,
                        preferred_element_type=jnp.float32)
    if scale != 1.0:
        logits = logits * scale
    lse = jax.nn.logsumexp(logits, axis=-1)
    at_target = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return logits, lse, lse - at_target


def _chunked(hidden, targets, weights, chunks: Tuple[int, int]):
    """``[B, S, ...]`` as ``[count, B, length, ...]`` for a scan over
    ``chunks`` (``loss_chunks``'s count and length) of the sequence; the
    padding weighs nothing."""
    count, length = chunks
    pad = count * length - hidden.shape[1]

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(a.shape[0], count, length, *a.shape[2:])
        return jnp.moveaxis(a, 1, 0)
    return split(hidden), split(targets), split(weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_nll(hidden, embedding, targets, weights, scale, chunks):
    """``sum(weights * nll)`` over ``[B, S]``, one of ``chunks`` of the
    sequence at a time; the logits are the tied head's times ``scale``."""
    table = embedding.astype(hidden.dtype)

    def one(total, chunk_of):
        h, t, w = chunk_of
        return total + (w * _chunk_nll(h, table, t, scale)[2]).sum(), None
    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32),
                            _chunked(hidden, targets, weights, chunks))
    return total


def _weighted_nll_fwd(hidden, embedding, targets, weights, scale, chunks):
    """The same pass over a chunk's logits gives its gradients too:
    ``(softmax - onehot) * weights`` (times ``scale``, the logits'
    own factor), cast to the compute dtype as autodiff's transpose of
    the logits' ``astype`` does, times the embedding (to the hidden
    states) and times the hidden states (to the embedding, summed over
    chunks in fp32).  Those two arrays are
    the residuals; no chunk's logits outlive its step of the scan."""
    table = embedding.astype(hidden.dtype)

    def one(carry, chunk_of):
        total, d_table = carry
        h, t, w = chunk_of
        logits, lse, nll = _chunk_nll(h, table, t, scale)
        hit = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2) == t[..., None]
        d_logits = jnp.exp(logits - lse[..., None]) - hit
        d_logits = d_logits * w[..., None]
        if scale != 1.0:
            d_logits = d_logits * scale
        d_logits = d_logits.astype(hidden.dtype)
        d_h = jnp.einsum("bcv,vh->bch", d_logits, table)
        d_table = d_table + jnp.einsum(
            "bcv,bch->vh", d_logits, h,
            preferred_element_type=jnp.float32)
        return (total + (w * nll).sum(), d_table), (d_h, nll)

    (total, d_table), (d_hidden, nll) = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32),
              jnp.zeros(embedding.shape, jnp.float32)),
        _chunked(hidden, targets, weights, chunks))

    def whole(a):  # [count, B, length, ...] back to [B, S, ...]
        a = jnp.moveaxis(a, 0, 1)
        a = a.reshape(a.shape[0], -1, *a.shape[3:])
        return a[:, :hidden.shape[1]]
    return total, (whole(d_hidden), d_table.astype(embedding.dtype),
                   whole(nll))


def _weighted_nll_bwd(scale, chunks, residuals, g):
    del scale, chunks  # the residuals carry both
    d_hidden, d_embedding, nll = residuals
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_embedding).astype(d_embedding.dtype), None, g * nll)


_weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def chunked_lm_loss(hidden, embedding, input_ids, mask=None,
                    logits_scale: float = 1.0,
                    sequences: Optional[int] = None):
    """``lm_loss`` of the tied head's logits (times ``logits_scale``,
    for a model that scales them) without the logits: from
    the final hidden states ``[B, S, H]`` and the token embedding ``[V,
    H]`` (``GPTLMHeadModel.hidden_and_embedding``), a chunk of the
    sequence at a time, so that the batch stays sharded as it is and the
    vocabulary stays whole.  A chunk holds at most ``LOSS_CHUNK_TOKENS``
    tokens of one device (``loss_chunks``): under GSPMD ``B`` is the
    global batch, so a sharded step says how many ``sequences`` of it
    one device holds (the step builders read that off their mesh); all
    ``B`` where nothing is said.  A chunk's fp32 logits are made once,
    where a gradient is asked for too (a custom VJP)."""
    targets = jnp.roll(input_ids, -1, axis=1)
    # Position t is weighed by its TARGET's mask; the last has none.
    counts = (jnp.ones(input_ids.shape, jnp.float32) if mask is None
              else jnp.roll(mask, -1, axis=1).astype(jnp.float32))
    counts = counts.at[:, -1].set(0.0)
    total = counts.sum()
    if mask is not None:
        total = jnp.maximum(total, 1.0)
    batch, seq = input_ids.shape
    return _weighted_nll(hidden, embedding, targets, counts / total,
                         float(logits_scale),
                         loss_chunks(seq, sequences or batch))
