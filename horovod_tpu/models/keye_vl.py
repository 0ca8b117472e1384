"""The language model of Keye-VL-2.0 (``model_type`` ``KeyeVL2``, as
Kwai-Keye/Keye-VL-2.0-30B-A3B publishes it; the vision tower is not
built): grouped-query attention over the keys a learned INDEXER keeps
for each query (DeepSeek-Sparse-Attention, ``sa_config``), rotary
positions in three sections, and in every layer top-k of softmax-routed
experts with no shared expert.  ``rms(x) = x / sqrt(mean(x^2) + eps)``;
no bias in any projection.

* Embedding ``h = E[ids]``; the head is a matrix of its own: ``logits =
  (rms(h) * w_final) @ W_head``.
* Every layer: ``a = h + attn(u)`` with ``u = rms_1(h)``, then ``h = a +
  ffn(rms_2(a))``.
* ``attn(u)``: ``q = u W_q`` as ``num_attention_heads`` heads, ``k = u
  W_k`` and ``v = u W_v`` as ``num_key_value_heads`` heads; ``q = rms(q)
  * w_q`` and ``k = rms(k) * w_k`` over each head's own width; both
  rotated in the rotate-half pairing, frequency pair ``i`` of a head
  reading the position stream its section names (``mrope_section``;
  text gives three equal streams).  Each key-value head serves a run of
  consecutive query heads; ``o_t = softmax over s in S_t of (q_t . k_s /
  sqrt(d)) v_s``; ``o W_o``.
* The indexer reads ``sg(u)`` (``stop_gradient``): ``q_I = sg(u) W_Iq``
  as ``indexer_num_heads`` heads of ``indexer_head_dim``, ``k_I =
  LayerNorm(sg(u) W_Ik)``, ONE head, both rotated over their whole width
  by stream 0; ``w = sg(u) W_Iw / sqrt(heads x dim)``; ``I[t, s] = sum_j
  w[t, j] relu(q_I[t, j] . k_I[s])``.  ``S_t``: the keys of the ``topk``
  largest ``I[t, s]``, ``s <= t``; every causal key where ``t < topk``
  (``ops/dsa.py`` ``select``; no gradient).
* ``ffn``: ``parallel/moe.py`` ``routed_experts`` under ``softmax_top_k``:
  the ``num_experts_per_tok`` largest of a softmax over ``num_experts``,
  divided by their sum (``norm_topk_prob``).  This model holds
  ``experts_held`` of the routed experts from ``first_expert`` on.
* The objective has two parts (``training.keye_vl_step_loss``): the
  next-token loss, whose gradient reaches everything but the indexer
  through the chosen keys alone, and in every layer ``L_I = mean_t
  KL(pbar[t, S_t] || softmax(I[t, S_t]))`` with ``pbar`` the mean over
  the heads of the main attention's own probabilities, detached
  (``ops/dsa.py`` ``indexer_loss``), whose gradient reaches the
  indexer's three matrices and its norm alone.  A layer sows its
  ``L_I`` as ``indexer_loss`` and its indexer's own selection as
  ``selected``.

Initial weights.  Under Flax's defaults (an embedding of norm a third,
branches that write vectors of norm ten) every token's hidden state is
mostly what the random branches add, which is nearly the same for every
token, and from the second layer on every token chooses the SAME eight
experts (the fullest expert 16 times the mean; the pairs that fall on
the experts held 0.1 to 2.9 times the expected, by seed).  A trained
model's routers see tokens that differ.  So the embedding is drawn at a
standard deviation of 1 and the two matrices that write into the
residual stream (attention's ``out``, the experts' ``down``) at ``1 /
sqrt(2 x init_depth)`` of ``lecun_normal``'s scale, GPT-2's rule at the
published depth: the token's own vector outweighs what the layers add,
and the experts' loads come out within a few per cent of even.

TPU-first like ``afmoe.py``: matmuls in ``dtype`` (bfloat16) from
float32 parameters; the router, the norms, the rotation, the index
weights, the thresholds and the alignment loss in float32.  On one TPU
device the selection, the attention over it and the alignment loss are
Pallas kernels (``hvd_dsa_select``, ``hvd_flash_fwd_selected`` /
``hvd_flash_bwd_selected``, ``hvd_dsa_indexer_loss``); elsewhere XLA's
walks over blocks of queries and einsums under the unpacked mask.  A
caller may hand every layer its selection (``given_selections``) as it
may hand the sparse layers their choice.  With ``remat`` a layer is
recomputed in the backward pass but for the kernels' outputs, the
selection, the routers' choice and what the device has room for
(``REMAT_CANDIDATES``).  Parameter names are matched by
:func:`horovod_tpu.parallel.sharding.keye_vl_partition_rules`.
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

from ..ops import dsa
from ..parallel import moe
from . import layers
from .layers import (FLASH_NAMES, GIVEN, RMSNorm, SparseFFN, attention_impl,
                     mesh_of, prefixes, recomputed, rotary_tables, rotate,
                     scaled_lecun_normal)

# The three projections attention reads its input through, by
# ``checkpoint_name``.
ATTENTION_IN_NAME = "attention_in"
# What a recomputed layer may keep beside what is always kept, dearest
# to recompute a byte first: attention's three input projections, the
# routed experts' gate and up, the sorted rows (a gather).
MATMUL_NAMES = (ATTENTION_IN_NAME, moe.EXPERT_GATE_UP_NAME, moe.ROWS_NAME)
# Always kept: the flash kernels' output, the selection with its
# statistics and the alignment loss's gradients (``ops/dsa.py``), and
# the routers' choice, none of which a recomputed pass may make again.
KEPT_NAMES = FLASH_NAMES + dsa.DSA_NAMES + (moe.CHOICE_NAME,)
REMAT_NAMES = KEPT_NAMES + MATMUL_NAMES
REMAT_CANDIDATES = prefixes(REMAT_NAMES, len(KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768     # a routed expert's
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    # Frequency pairs of a head that read each of the position streams.
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    # ``sa_config``: the indexer and how many keys a query keeps.
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    # The router's width, and the run of routed experts this model holds.
    num_experts: int = 128
    num_experts_per_tok: int = 8
    experts_held: int = 128
    first_expert: int = 0
    norm_topk_prob: bool = True
    # The depth the initial scale of the matrices that write into the
    # residual stream follows, ``1 / sqrt(2 x depth)`` (GPT-2's rule):
    # the published depth, however many layers this model holds.
    init_depth: int = 48
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # As ``GraniteConfig``'s.
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError("the experts held, first_expert to first_expert "
                             "+ experts_held - 1, lie among num_experts")
        if self.head_dim % 2 or self.indexer_head_dim % 2:
            raise ValueError("rotary positions pair a head's halves")
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError("mrope_section gives every one of a head's %d "
                             "frequency pairs a stream"
                             % (self.head_dim // 2))
        if self.topk < 1:
            raise ValueError("a query keeps one key at least")


    @property
    def residual_scale(self) -> float:
        return (2.0 * self.init_depth) ** -0.5


def keye_vl_tiny_config(**kw) -> KeyeVLConfig:
    """Tiny stack for tests and dry runs: two layers, 4 query heads over
    2 key-value heads of 16 in sections of 2, 3 and 3 pairs, an indexer
    of 3 heads of 8 that keeps 24 keys a query, 8 routed experts of
    which 4 are held, top 2."""
    defaults = dict(vocab_size=512, hidden_size=64, moe_intermediate_size=32,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    mrope_section=(2, 3, 3), indexer_num_heads=3,
                    indexer_head_dim=8, topk=24, num_experts=8,
                    num_experts_per_tok=2, experts_held=4)
    defaults.update(kw)
    return KeyeVLConfig(**defaults)


def _attend_selected(q, k, v, selected, scale: float, dtype):
    """The einsum path: every score made, those the selection leaves
    out masked.  Returns the heads' output and each row's log-sum-exp
    ``[B, H, S]`` float32."""
    group = q.shape[2] // k.shape[2]
    seq, q_heads, head_dim = q.shape[1:]
    keep = dsa.unpack_mask(selected)[:, None, None]        # [B, 1, 1, q, s]
    grouped = q.reshape(*q.shape[:2], k.shape[2], group, head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", grouped, k) * scale
    scores = jnp.where(keep, scores.astype(jnp.float32), -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None])
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(dtype), v)
    return (ctx.reshape(*ctx.shape[:2], q_heads, head_dim),
            lse.reshape(lse.shape[0], q_heads, seq))


class IndexedAttention(nn.Module):
    """Grouped-query attention over the keys the indexer keeps, queries
    and keys normed over their head and rotated by sections; the layer's
    alignment loss sown beside it."""
    config: KeyeVLConfig
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, tables, index_tables):
        cfg = self.config
        q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        heads = lambda count, width, name: nn.DenseGeneral(
            features=(count, width), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        q = checkpoint_name(heads(q_heads, head_dim, "query")(x),
                            ATTENTION_IN_NAME)
        k = checkpoint_name(heads(kv_heads, head_dim, "key")(x),
                            ATTENTION_IN_NAME)
        v = checkpoint_name(heads(kv_heads, head_dim, "value")(x),
                            ATTENTION_IN_NAME)
        with jax.named_scope("qk_norm"):
            q, k = norm("query_norm")(q), norm("key_norm")(k)
        with jax.named_scope("rotary"):
            q, k = rotate(q, *tables), rotate(k, *tables)

        # The indexer learns from its own loss alone: its input is the
        # layer's, detached.
        u = jax.lax.stop_gradient(x)
        with jax.named_scope("indexer"):
            q_i = heads(cfg.indexer_num_heads, cfg.indexer_head_dim,
                        "indexer_query")(u)
            dense = lambda width, name, dtype: nn.Dense(
                width, use_bias=False, dtype=dtype, param_dtype=jnp.float32,
                name=name)
            k_i = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                               param_dtype=jnp.float32,
                               name="indexer_key_norm")(
                dense(cfg.indexer_head_dim, "indexer_key", cfg.dtype)(u))
            q_i = rotate(q_i, *index_tables)
            k_i = rotate(k_i.astype(cfg.dtype)[:, :, None],
                         *index_tables)[:, :, 0]
            w = dense(cfg.indexer_num_heads, "indexer_weights",
                      jnp.float32)(u) * (
                cfg.indexer_num_heads * cfg.indexer_head_dim) ** -0.5

        mesh = mesh_of(self.heads_sharding)
        one_device = mesh is None or mesh.size == 1
        kernels = attention_impl(
            cfg, mesh, one_device and not self.is_initializing()) == "flash"
        scale = head_dim ** -0.5
        # The indexer's own selection, which a caller may look at
        # (``selections``) even where it hands the layer another.
        selected, lse_i = dsa.select(q_i, k_i, w, cfg.topk, kernels=kernels)
        self.sow("intermediates", "selected", selected)
        if self.has_variable(GIVEN, "selected"):
            selected = self.get_variable(GIVEN, "selected")
            with jax.named_scope("select"):
                lse_i = dsa.selected_lse(q_i, k_i, w, selected)
        if kernels:
            from ..ops.pallas_attention import flash_attention_selected
            group = q_heads // kv_heads
            ctx, lse = flash_attention_selected(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                selected, cfg.topk, scale=scale)
            ctx = ctx.astype(cfg.dtype)
        else:
            ctx, lse = _attend_selected(q, k, v, selected, scale, cfg.dtype)
        loss = dsa.indexer_loss(q_i, k_i, w, q, k, lse, selected, lse_i,
                                scale, kernels=kernels)
        self.sow("intermediates", "indexer_loss", loss)
        return nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32,
            kernel_init=scaled_lecun_normal(cfg.residual_scale),
            name="out")(ctx)


def sparse_ffn(config: KeyeVLConfig, mesh) -> SparseFFN:
    """The routed experts of a layer under a softmax router; no shared
    expert."""
    return SparseFFN(
        experts=config.num_experts, held=config.experts_held,
        first_expert=config.first_expert, top_k=config.num_experts_per_tok,
        width=config.moe_intermediate_size, normalize=config.norm_topk_prob,
        dtype=config.dtype, router=moe.softmax_top_k,
        down_scale=config.residual_scale, mesh=mesh, name="moe")


class KeyeVLLayer(nn.Module):
    config: KeyeVLConfig
    heads_sharding: Optional[NamedSharding] = None

    @nn.compact
    def __call__(self, x, tables, index_tables):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + IndexedAttention(cfg, self.heads_sharding, name="attention")(
            norm("input_norm")(x), tables, index_tables)
        return x + sparse_ffn(cfg, mesh_of(self.heads_sharding))(
            norm("post_attention_norm")(x))


class KeyeVLLMHeadModel(nn.Module):
    """The stack and its untied head."""
    config: KeyeVLConfig
    heads_sharding: Optional[NamedSharding] = None
    # What a recomputed layer keeps (``config.remat``); the step
    # builder hands over what fits its shapes and its device.
    remat_names: Tuple[str, ...] = REMAT_NAMES

    @nn.compact
    def hidden_and_embedding(self, input_ids, positions=None):
        """The final hidden states ``[B, S, H]`` (after the last norm)
        and the head's matrix as ``chunked_lm_loss`` takes an embedding,
        ``[V, H]``.  ``positions`` ``[3, S]``: the three position
        streams; text's three are the token's place, which is the
        default."""
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=jnp.float32,
                       embedding_init=nn.initializers.normal(1.0),
                       name="word_embeddings")
        head = self.param(
            "lm_head", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = wte(input_ids)
        seq = input_ids.shape[1]
        with jax.named_scope("rotary_tables"):   # once a step
            tables = rotary_tables(seq, cfg.head_dim, cfg.rope_theta,
                                   cfg.mrope_section, positions)
            index_tables = rotary_tables(
                seq, cfg.indexer_head_dim, cfg.rope_theta,
                positions=None if positions is None else positions[:1])
        layer = recomputed(KeyeVLLayer, cfg.remat, self.remat_names)
        for i in range(cfg.num_hidden_layers):
            x = layer(cfg, self.heads_sharding, name=f"layer_{i}")(
                x, tables, index_tables)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return x, head

    def __call__(self, input_ids, positions=None):
        x, head = self.hidden_and_embedding(input_ids, positions)
        return jnp.einsum("bsh,vh->bsv", x, head.astype(self.config.dtype),
                          preferred_element_type=jnp.float32)


expert_choices = functools.partial(layers.expert_choices, KeyeVLLMHeadModel)


def given_selections(selected) -> dict:
    """``{layer index: packed mask [B, S / 32, S]}`` as the variables
    that make every attention layer take that selection and not its
    indexer's own, to be merged with ``layers.given_choices``'s:
    ``{GIVEN: {"layer_<i>": {"attention": {"selected": mask}}}}``."""
    return {GIVEN: {"layer_%d" % i: {"attention": {"selected": s}}
                    for i, s in selected.items()}}


def given(chosen=None, selected=None) -> dict:
    """The ``given`` variables of an ``apply`` that hands the sparse
    layers their choice of experts (``{layer: [T, top_k]}``), the
    attention layers their selection of keys (``{layer: packed mask}``),
    both or neither."""
    merged = {}
    for part in (layers.given_choices(chosen or {})[GIVEN],
                 given_selections(selected or {})[GIVEN]):
        for name, value in part.items():
            merged.setdefault(name, {}).update(value)
    return {GIVEN: merged} if merged else {}


def sown_of(state, name: str) -> dict:
    """``{layer index: value}`` of what every layer's attention sowed as
    ``name`` (``indexer_loss``, ``selected``) in an ``apply`` with
    ``mutable=["intermediates"]``."""
    return {int(layer.split("_")[1]): sown["attention"][name][0]
            for layer, sown in state["intermediates"].items()}


def selections(config: KeyeVLConfig, params, input_ids, chosen=None) -> dict:
    """``{layer index: packed mask}``: the keys every query of
    ``input_ids`` keeps in every layer of the model as it runs."""
    model = KeyeVLLMHeadModel(dataclasses.replace(config, remat=False))
    _, state = model.apply(
        {"params": params, **given(chosen)}, input_ids,
        mutable=["intermediates"], method="hidden_and_embedding")
    return sown_of(state, "selected")


def remat_bytes(names, sequences: int, seq: int, config: KeyeVLConfig) -> int:
    """Bytes one device keeps across ``remat`` for ``names``, with
    ``sequences`` sequences of ``seq`` on it.  Tensor parallelism is not
    counted: the figure errs high."""
    itemsize = np.dtype(config.dtype).itemsize
    depth, heads = config.num_hidden_layers, config.num_attention_heads
    index = config.indexer_num_heads * config.indexer_head_dim
    rows = moe.dispatch_rows(1, config.num_experts_per_tok,
                             config.experts_held)   # of one token
    per_token = {
        "flash_out": depth * heads * config.head_dim * itemsize,
        "flash_lse": depth * heads * 4,
        # A query's column of the packed mask: a bit a key.
        dsa.SELECTED_NAME: depth * seq // 8,
        dsa.LSE_NAME: depth * 4,
        dsa.GRADS_NAME: depth * (
            (index + config.indexer_head_dim) * itemsize
            + config.indexer_num_heads * 4),
        moe.CHOICE_NAME: depth * config.num_experts_per_tok * 4,
        ATTENTION_IN_NAME: depth * itemsize * config.head_dim
        * (heads + 2 * config.num_key_value_heads),
        moe.EXPERT_GATE_UP_NAME: depth * rows * 2
        * config.moe_intermediate_size * itemsize,
        moe.ROWS_NAME: depth * rows * config.hidden_size * itemsize}
    return sequences * seq * sum(per_token[name] for name in names)
