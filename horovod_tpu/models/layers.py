"""What the causal-LM families share and none of them owns: the flash
dispatch, the norm, the gated MLP, the short convolution, rotary
positions, the sparse feed-forward, the rule for what a recomputed
layer keeps and the loss over chunks of the sequence.

A family's module (``gpt.py``, ``granite.py``, ``lfm2.py``,
``deepseek_v3.py``, ``qwen3_next.py``, ``afmoe.py``, ``keye_vl.py``)
imports from here, from ``ops/`` and from ``parallel/``, never from
another family's: a layer two families need lives here from the day the
second one needs it.  Every
module below is built with an explicit ``name=`` by its caller, so a
parameter's path says nothing of this file.
"""

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding

from ..parallel import moe

# What the flash kernels name (``checkpoint_name``): a recomputed layer
# always keeps them, recomputing them is the forward kernel over again.
FLASH_NAMES = ("flash_out", "flash_lse")
# The collection of variables by which a caller hands the sparse layers
# a choice of experts (``given_choices``).
GIVEN = "given"
# Tokens one device holds in one chunk of ``chunked_lm_loss``.  2048 is
# 16 sequences x 128 positions, chosen on the v5e at 16 x 1024 (PERF.md
# PR 26): 2048 x 50257 fp32 logits are 0.41 GB where all 1024 positions
# were 3.29, and a chunk's products still feed the MXU.  Counted in
# tokens and not in positions since PR 35: every chunk reads and writes
# the whole fp32 gradient of the embedding, so two sequences of 4096
# walk 4 chunks of 1024 positions, not 32 of 128.
LOSS_CHUNK_TOKENS = 2048


def mesh_of(sharding: Optional[NamedSharding]) -> Optional[Mesh]:
    """The mesh a step builder's sharding lies on; None where the model
    is applied directly."""
    return None if sharding is None else sharding.mesh


def _flash_causal(q, k, v, sharding: Optional[NamedSharding],
                  scale: Optional[float] = None,
                  window: Optional[int] = None):
    from ..ops.pallas_attention import flash_attention
    attend = functools.partial(flash_attention, causal=True, scale=scale,
                               window=window)
    if sharding is not None and sharding.mesh.size > 1:
        # GSPMD does not partition a Mosaic kernel; attention is
        # independent per sequence and per head, so each chip runs
        # the kernels on the shard the step builder gives it.
        attend = jax.shard_map(
            attend, mesh=sharding.mesh, in_specs=(sharding.spec,) * 3,
            out_specs=sharding.spec, check_vma=False)
    return attend(q, k, v)


def attention_impl(config, mesh, kernels_apply: bool) -> str:
    """``config.attention_impl`` with "auto" resolved: the kernels on a
    TPU (the mesh's platform, or the default backend where there is no
    mesh) wherever they apply, the einsums elsewhere."""
    if config.attention_impl != "auto":
        return config.attention_impl
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    return "flash" if platform == "tpu" and kernels_apply else "einsum"


def visible_keys(seq: int, window: Optional[int] = None):
    """``[seq, seq]`` bool, queries down and keys across: key ``j`` is
    visible to query ``i`` where ``0 <= i - j`` and, with a ``window``,
    ``i - j < window`` (the query's own position counts as one of the
    window's keys).  The einsum paths' mask; the kernels decide the same
    tile by tile (``ops/pallas_attention.py``)."""
    keep = jnp.tril(jnp.ones((seq, seq), bool))
    if window is not None:
        keep = keep & jnp.triu(jnp.ones((seq, seq), bool), 1 - window)
    return keep


def grouped_causal_attention(q, k, v, scale: float, config,
                             heads_sharding: Optional[NamedSharding],
                             initializing: bool,
                             window: Optional[int] = None):
    """Causal ``softmax(q k^T scale) v`` where each key-value head
    serves a run of consecutive query heads; with a ``window`` over the
    last ``window`` keys up to the query's own (``visible_keys``).
    ``q``: ``[B, S, heads, D]``; ``k``, ``v``: ``[B, S, kv_heads, D]``.
    The flash kernels where ``attention_impl`` says so (never for
    ``init``, which wants the parameters' shapes and nothing of the
    attention): they take as many key-value heads as query heads, so
    each is laid out once for every query head it serves, and they walk
    a window's band and nothing left of it.  Grouped einsums under the
    mask elsewhere."""
    group = q.shape[2] // k.shape[2]
    if attention_impl(config, mesh_of(heads_sharding),
                      not initializing) == "flash":
        ctx = _flash_causal(q, jnp.repeat(k, group, axis=2),
                            jnp.repeat(v, group, axis=2), heads_sharding,
                            scale=scale, window=window)
        return ctx.astype(config.dtype)
    seq, q_heads, head_dim = q.shape[1:]
    q = q.reshape(*q.shape[:2], k.shape[2], group, head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * scale
    causal = visible_keys(seq, window)
    scores = jnp.where(causal, scores, jnp.finfo(config.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(config.dtype), v)
    return ctx.reshape(*ctx.shape[:2], q_heads, head_dim)


def recomputed(layer, remat: bool, names: Tuple[str, ...], **kw):
    """``layer`` (a module class) as a stack builds it: with ``remat``
    recomputed in the backward pass but for the ``checkpoint_name``s
    ``names``."""
    if not remat:
        return layer
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    return nn.remat(layer, policy=policy, **kw)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        return (x * scale).astype(self.dtype)


class GatedMLP(nn.Module):
    """``(silu(x @ W_gate) * (x @ W_up)) @ W_out``, ``width`` wide; no
    bias."""
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        # ``W_in``'s two halves as two matrices: a tensor-parallel axis
        # splits both alike, and each is a plain matmul (as one
        # parameter of [hidden, 2, intermediate] or [2, hidden,
        # intermediate] XLA wrote the weight's gradient and both of
        # AdamW's moments in another layout and copied them back, 18
        # ms a step at the published widths).
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype,
            param_dtype=jnp.float32, name=name)
        gate = checkpoint_name(dense(self.width, "gate")(x), "gate_up")
        up = checkpoint_name(dense(self.width, "up")(x), "gate_up")
        return dense(x.shape[-1], "out")(nn.silu(gate) * up)


def causal_depthwise_conv(x, kernel, bias):
    """``out_t = bias + sum_k kernel[k] * x_{t - (K - 1) + k}`` per
    channel, zeros before the sequence.  ``x``: ``[B, S, C]``;
    ``kernel``: ``[K, C]``; ``bias``: ``[C]``, or None for none."""
    taps, seq = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(x.dtype)
    for k in range(taps):
        tap = padded[:, k:k + seq] * kernel[k].astype(x.dtype)
        out = tap if out is None else out + tap
    return out


def rotary_tables(seq: int, head_dim: int, theta: float,
                  sections: Optional[Sequence[int]] = None,
                  positions=None):
    """``cos`` and ``sin`` of ``position x theta^(-2i / head_dim)``,
    each ``[seq, head_dim / 2]`` in float32.  ``positions`` ``[streams,
    seq]`` gives every position stream its own numbers and ``sections``
    says how many consecutive frequency pairs ``i`` read each stream
    (three sections of 16, 24 and 24: pair ``i`` of 64 reads stream 0
    under 16, stream 1 under 40, stream 2 from there); with no
    ``positions`` every stream counts 0, 1, 2, ..., which is the one
    stream's tables whatever the sections."""
    inverse = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if positions is None:
        angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inverse
        return jnp.cos(angles), jnp.sin(angles)
    positions = jnp.asarray(positions, jnp.float32).reshape(-1, seq)
    sections = tuple(sections or (head_dim // 2,))
    if (len(sections) != positions.shape[0]
            or sum(sections) != head_dim // 2):
        raise ValueError(
            "sections %r give each of %d position streams its share of the "
            "%d frequency pairs" % (sections, positions.shape[0],
                                    head_dim // 2))
    stream = np.repeat(np.arange(len(sections)), sections)
    angles = positions[stream].T * inverse
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """Rotary positions in the rotate-half pairing: channel ``i`` of a
    head turns with channel ``i + head_dim / 2``.  ``x``: ``[B, S,
    heads, head_dim]``; float32 inside, ``x``'s type out."""
    first, second = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1).astype(x.dtype)


def scaled_lecun_normal(scale: float = 1.0, **axes):
    """``lecun_normal`` at ``scale`` times its standard deviation; at 1
    the initialiser itself, value for value."""
    if scale == 1.0:
        return nn.initializers.lecun_normal(**axes)
    return nn.initializers.variance_scaling(
        scale * scale, "fan_in", "truncated_normal", **axes)


class SparseFFN(nn.Module):
    """The parameters of ``parallel.moe.routed_experts``: a router over
    all ``experts``, and the stacked matrices of the ``held`` from
    ``first_expert`` on; beside them, where ``shared`` is a width, the
    always-on shared expert, one gated MLP named ``shared`` (the
    module's name is its scope), times a sigmoid of the token where
    ``shared_gate`` says so.  On one TPU device (the platform read as
    the attention's is) the routed layer's wide passes are Pallas
    kernels; ``init`` wants the parameters' shapes and nothing of the
    layer, so no kernel is traced for it.  A family builds it from its
    configuration, under the name ``moe``."""
    experts: int
    held: int
    first_expert: int
    top_k: int
    width: int              # a routed expert's
    normalize: bool
    dtype: Any
    # How tokens choose (``moe.routed_experts``): None is sigmoid scores
    # plus the selection bias ``expert_bias``, the gates times ``scale``
    # over their sum plus ``gate_sum_eps``; ``moe.softmax_top_k`` reads
    # none of the three.
    router: Optional[Callable] = None
    scale: float = 1.0
    gate_sum_eps: float = moe.GATE_SUM_EPS
    shared: int = 0
    shared_gate: bool = False
    # The factor on the initial scale of the stack that writes into the
    # residual stream (``down``); 1 is every other matrix's.
    down_scale: float = 1.0
    # The mesh the step this model is traced in lays its arrays on (the
    # step builder says, through ``heads_sharding``); None where the
    # model is applied directly.
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        hidden, width = x.shape[-1], self.width
        stacked = lambda name, fan_in, fan_out, scale=1.0: self.param(
            name, scaled_lecun_normal(scale, batch_axis=(0,)),
            (self.held, fan_in, fan_out), jnp.float32)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (hidden, self.experts), jnp.float32)
        # A buffer in the published models: it selects, no gradient
        # reaches it, and AdamW's update of a zero gradient is zero (no
        # decay on a vector).  What moves it, in a family whose row says
        # so, is the step's own rule after the optimizer's update
        # (``moe.moved_bias`` on the counts of the choice sown below).
        bias = None if self.router else self.param(
            "expert_bias", nn.initializers.zeros, (self.experts,),
            jnp.float32)
        # A caller may hand the choice over, as the variable ``chosen``
        # of the collection ``given`` (``given_choices``).
        given = (self.get_variable(GIVEN, "chosen")
                 if self.has_variable(GIVEN, "chosen") else None)
        y, routing = moe.routed_experts(
            x.reshape(-1, hidden), router, bias,
            stacked("gate", hidden, width), stacked("up", hidden, width),
            stacked("down", width, hidden, self.down_scale),
            first_expert=self.first_expert, top_k=self.top_k,
            normalize=self.normalize, scale=self.scale,
            gate_sum_eps=self.gate_sum_eps, chosen=given,
            router=self.router,
            kernels=moe.on_one_tpu(self.mesh) and not self.is_initializing())
        self.sow("intermediates", "chosen", routing.chosen)
        if not self.shared:
            return y.reshape(x.shape)
        shared = GatedMLP(self.shared, self.dtype, name="shared")
        if not self.shared_gate:
            return y.reshape(x.shape) + shared(x)
        shared = shared(x)
        with jax.named_scope("shared"):
            open_ = nn.Dense(1, use_bias=False, dtype=jnp.float32,
                             param_dtype=jnp.float32, name="shared_gate")(x)
            shared = shared * jax.nn.sigmoid(open_).astype(self.dtype)
        return y.reshape(x.shape) + shared


def choices_of(state) -> dict:
    """``{layer index: [T, top_k] int32}`` from what an ``apply`` with
    ``mutable=["intermediates"]`` returned beside its value: what the
    ``moe`` module of every sparse ``layer_<i>`` sowed as ``chosen``."""
    return {int(name.split("_")[1]): layer["moe"]["chosen"][0]
            for name, layer in state["intermediates"].items()}


def sown_choices(model: nn.Module, params, input_ids):
    """``choices_of`` what ``model`` sowed on ``input_ids``."""
    _, state = model.apply(
        {"params": params}, input_ids, mutable=["intermediates"],
        method="hidden_and_embedding")
    return choices_of(state)


def expert_choices(model_class, config, params, input_ids):
    """``{layer index: [T, top_k] int32}``: the experts, of all the
    router's, that each token chose in every sparse layer of
    ``model_class(config)`` (a family's ``expert_choices`` is this over
    its model)."""
    return sown_choices(model_class(dataclasses.replace(config, remat=False)),
                        params, input_ids)


def given_choices(chosen) -> dict:
    """``expert_choices``'s ``{layer index: [T, top_k]}`` as the
    variables that make every sparse layer take that choice and not its
    own: ``model.apply({"params": params, **given_choices(chosen)},
    ...)``."""
    return {GIVEN: {"layer_%d" % i: {"moe": {"chosen": c}}
                    for i, c in chosen.items()}}


def counts_by_expert(chosen, num_experts: int):
    """``[num_experts] int32``: how many of the ``T x top_k`` choices
    ``chosen`` fell on each expert."""
    return jnp.bincount(chosen.reshape(-1), length=num_experts)


def prefixes(names: Tuple[str, ...], kept: int) -> Tuple[Tuple[str, ...], ...]:
    """``names`` and every shorter prefix of it down to its first
    ``kept``, longest first: the candidates of a family whose
    recomputed layer gives up its names one at a time, last first."""
    return tuple(names[:count] for count in range(len(names), kept - 1, -1))


def kept_across_remat(candidates: Sequence[Tuple[str, ...]],
                      bytes_of: Callable, state_bytes: int,
                      memory_limit: Optional[int]) -> Tuple[str, ...]:
    """The names a recomputed layer keeps: the first of ``candidates``
    (tuples of ``checkpoint_name``s, the most first, the last what is
    always kept) whose ``bytes_of(names)`` fit one device's
    ``memory_limit`` bytes beside the state the step is handed
    (``state_bytes``: parameters and optimizer state on that device)
    and a margin of a quarter of the memory (the gradients, the layers'
    inputs, a chunk of the loss, the compiler's own temporaries: 1.9 GB
    of 16.9 in GPT's cell); the last where none does, and the first
    where the device reports no limit (a CPU, a chip that is only
    described)."""
    if memory_limit is None:
        return candidates[0]
    for names in candidates[:-1]:
        if bytes_of(names) + state_bytes + memory_limit // 4 <= memory_limit:
            return names
    return candidates[-1]


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
    """``models.gpt.lm_loss`` of the head's logits (times
    ``logits_scale``, for a model that scales them) without the logits:
    from the final hidden states ``[B, S, H]`` and the head's matrix
    ``[V, H]`` (a model's ``hidden_and_embedding``: the token embedding
    where the head is tied to it), a chunk of the
    sequence at a time, so that the batch stays sharded as it is and the
    vocabulary stays whole.  A chunk holds at most ``LOSS_CHUNK_TOKENS``
    tokens of one device (``loss_chunks``): under GSPMD ``B`` is the
    global batch, so a sharded step says how many ``sequences`` of it
    one device holds (the step builder reads that off its mesh); all
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
