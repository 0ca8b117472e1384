"""The DeepSeek-V3 family (latent attention in every layer; a dense
SwiGLU or top-k routed experts beside a shared expert): how the
benchmark builds its step from the program, makes a batch from the seed,
counts the required FLOPs, and the flash kernels' at two widths, and
calls the reference.  Sizes come from the configuration file, never
from here.

The reference check and near-ties: as for the LFM2 family
(``benchmarks/models/lfm2.py`` has the reasons).  A token whose sixth
and seventh scores lie closer than bfloat16's rounding of the router's
input chooses differently in the program and in the float32 reference;
``reference_loss`` hands the reference the program's choice and holds
the choices themselves to ``choices_agree``, silently, and ``init``
prints what the routers did on the first batch of the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models import common
from benchmarks.models.lfm2 import host_batch, optimizer  # noqa: F401  the
#   same batches (tokens of the rows held) and the same AdamW
from benchmarks.reference import deepseek_v3 as reference
from benchmarks.trainers.common import info
from horovod_tpu.models import deepseek_v3
from horovod_tpu.models.lfm2 import counts_by_expert
from horovod_tpu.training import (MOE_PAIRS_HELD, deepseek_v3_step_loss,
                                  make_deepseek_v3_train_step)


def program_config(config: dict) -> deepseek_v3.DeepseekV3Config:
    """``n_routed_experts`` in the file counts the experts held; the
    router keeps the published width."""
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None:
        raise ValueError("the program has no low-rank query path and no "
                         "rotary scaling")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's model has a head of its own")
    return deepseek_v3.DeepseekV3Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_head_dim=config["qk_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        n_shared_experts=config["n_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def attention_flops_per_token(config: dict, attended: float) -> float:
    """One latent-attention operator: the four projections (query,
    down, up, out), and every head's scores over ``attended`` keys at
    the queries' width and weighted sum at the values'.  The norm, the
    rotation and the keys' layout are not matrix products and are not
    counted."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    qk, v = config["qk_head_dim"], config["v_head_dim"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    projections = (hidden * heads * qk + hidden * (latent + rope)
                   + latent * heads * (config["qk_nope_head_dim"] + v)
                   + heads * v * hidden)
    return 2 * projections + 2 * attended * heads * (qk + v)


def sparse_ffn_flops_per_token(config: dict) -> float:
    """The router over all experts, the shared expert at every token,
    and the routed experts held at the EXPECTATION under uniform
    routing: of a token's ``top_k`` choices ``held / total`` fall here
    (0.75 expert a token at 6 of 128 with 16 held).  Rows of the buffer
    that hold no pair are not counted."""
    hidden, total = config["hidden_size"], \
        config["published"]["n_routed_experts"]
    width = config["moe_intermediate_size"]
    expected = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / total
    return 2 * hidden * total \
        + (config["n_shared_experts"] + expected) * 3 * 2 * hidden * width


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step: latent attention
    in every layer, causal at half the square; the dense SwiGLU (three H
    x I products) or the sparse feed-forward; the head over the rows
    held at every position; recomputation, padded rows and padded lanes
    not counted."""
    hidden = config["hidden_size"]
    per_token = 2.0 * hidden * config["vocab_size"]
    for layer in range(config["num_hidden_layers"]):
        per_token += attention_flops_per_token(config, seq / 2)
        per_token += (3 * 2 * hidden * config["intermediate_size"]
                      if layer < config["first_k_dense_replace"]
                      else sparse_ffn_flops_per_token(config))
    return common.train_flops(per_token * batch * seq)


def flash_kernel_work(config: dict, batch: int, seq: int) -> dict:
    """``{kernel: (FLOPs, HBM bytes)}`` of one call of each flash kernel
    on ``[batch, seq, heads, qk | v]`` (one layer, one pass), causal at
    half the square: what the algorithm needs, not what the tiles on
    the diagonal spend.  The forward makes scores and the weighted sum;
    dQ makes scores, ``dP = dO V^T`` and ``dQ = dS K``; dK/dV makes
    scores, ``dV = P^T dO``, ``dP`` and ``dK = dS^T Q``.  Bytes: every
    operand read once and every result written once, the row statistics
    in float32."""
    heads, qk, v = (config["num_attention_heads"], config["qk_head_dim"],
                    config["v_head_dim"])
    itemsize = np.dtype(config["compute_dtype"]).itemsize
    square = 2.0 * batch * heads * seq * (seq / 2)   # one product, width 1
    rows = batch * seq * heads
    wide, narrow, stat = rows * qk * itemsize, rows * v * itemsize, rows * 4
    return {
        "hvd_flash_fwd": (square * (qk + v),
                          2 * wide + 2 * narrow + stat),
        "hvd_flash_bwd_dq": (square * (2 * qk + v),
                             3 * wide + 2 * narrow + 2 * stat),
        "hvd_flash_bwd_dkv": (square * (2 * qk + 2 * v),
                              3 * wide + 3 * narrow + 2 * stat)}


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_deepseek_v3_train_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"],
        weight_decay=config["optimizer"]["weight_decay"])

    report = jax.jit(lambda params, ids: routing_report(
        config, *routing_of(config, params, ids)))

    def init(key, batch):
        state = init_fn(key, batch["input_ids"])
        say(jax.device_get(report(state[0], batch["input_ids"])))
        return state

    def step(state, batch):
        params, opt_state, loss = step_fn(*state, batch["input_ids"])
        return (params, opt_state), loss

    def hlo_text(state, batch):
        return step_fn.lower(*state, batch["input_ids"]).compile().as_text()

    return common.InGraph(init, step, lambda state: state[0], hlo_text,
                          batch_sharding)


def init_params(config: dict, key, batch):
    return deepseek_v3.DeepseekV3LMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_deepseek_v3_train_step``'s step itself."""
    model = deepseek_v3.DeepseekV3LMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        return deepseek_v3_step_loss(model, params, batch["input_ids"])
    return loss


def program_choice(config: dict, params, ids):
    """The program's choice of experts on ``ids`` (``{layer: [T,
    top_k]}``, no gradient), from its forward pass as it runs, made the
    same way wherever it is asked for: the comparison's two sides are
    two compiled programs, and two compiles of one bfloat16 forward
    pass round it at other places and decide some near-ties the other
    way (PERF.md, PR 37: tokens of 16384 a layer, enough to move the
    router's gradient by half its tolerance).  So each side makes the
    choice in a forward pass of its own that a barrier keeps apart
    from the rest of its program, the same computation in both, and
    both compute on it."""
    params, ids = jax.lax.optimization_barrier(
        (jax.lax.stop_gradient(params), ids))
    # The program as it runs, not at the precision the comparison sets
    # around the reference (which its kernels would refuse).
    with jax.default_matmul_precision("default"):
        chosen = deepseek_v3.expert_choices(program_config(config), params,
                                            ids)
    return jax.lax.optimization_barrier(chosen)


def system_loss(config: dict):
    """The step's loss on the program's choice (``program_choice``)."""
    model = deepseek_v3.DeepseekV3LMHeadModel(program_config(config))

    def loss(params, batch):
        ids = batch["input_ids"]
        return deepseek_v3_step_loss(model, params, ids,
                                     program_choice(config, params, ids))
    return loss


# What the reference check holds the program's CHOICES to, since the
# reference computes on them.  A choice the reference would not have
# made must be a near-tie: the expert taken lies within NEAR_TIE under
# the least of the reference's own top k, in its own ``sigmoid + bias``.
# And such choices are few: at most 1 - MIN_AGREEMENT of a layer's.
# Each limit is the geometric mean of two readings on the chip at 2 x
# 8192 at the published widths (PERF.md, PR 37): the program's in
# bfloat16, at most 0.0132 and 1.98 % (1942 of 98304, the fifth sparse
# layer, over five batches; the choices that differ grow with depth,
# 1.1 % in the first), and those of a program whose weights keep three
# bits of mantissa, at least 0.0863 and 10.4 % (the first).
NEAR_TIE = 0.034
MIN_AGREEMENT = 0.955


def routing_of(config: dict, params, ids, program_params=None):
    """``(the program's choices, what the reference's routers saw on
    them)`` for a batch, each ``{layer: ...}``: the program as it runs,
    the reference in float32 at full precision.  ``program_params``
    gives the program other weights than the reference (a test of the
    limits rounds them)."""
    chosen = deepseek_v3.expert_choices(
        program_config(config),
        params if program_params is None else program_params, ids)
    with jax.default_matmul_precision("highest"):
        _, _, routing = reference.hidden_and_routing(
            params, {"input_ids": ids}, config, chosen)
    return chosen, routing


def routing_report(config: dict, chosen: dict, routing: dict) -> dict:
    """By sparse layer, from the program's choices (``{layer: [T,
    top_k]}``) and what the reference's router saw on them: how many
    the reference would not have made and the widest gap among those,
    the pairs that fell on the experts held beside the expectation that
    ``flops_per_step`` counts, and the fullest expert's load over the
    mean load of those held."""
    first, held = config.get("first_expert", 0), config["n_routed_experts"]
    total = config["published"]["n_routed_experts"]
    report = {}
    for layer, took in chosen.items():
        saw = routing[layer]
        same = (took[:, :, None] == saw["own"][:, None, :]).any(-1)
        counts = counts_by_expert(took, total)[first:first + held]
        report[layer] = {"choices": took.size,
                         "differ": took.size - same.sum(),
                         "widest_gap": saw["gap"].max(),
                         "pairs_held": counts.sum(),
                         "pairs_expected": took.size * held / total,
                         "fullest_over_mean": counts.max() / counts.mean()}
    return report


def choices_agree(report: dict):
    """Every layer's choices within the two limits above."""
    return jnp.all(jnp.stack(
        [(r["differ"] <= (1.0 - MIN_AGREEMENT) * r["choices"])
         & (r["widest_gap"] <= NEAR_TIE) for r in report.values()]))


def say(report: dict):
    """The report's lines, and the gauge."""
    for layer, r in sorted(report.items()):
        r = {k: float(v) for k, v in r.items()}
        MOE_PAIRS_HELD.set(r["pairs_held"], layer=str(layer))
        info("sparse layer %d: %d of %d choices are not the float32 "
             "reference's own (at most %d may), the widest gap %.2e (limit "
             "%.1e); %d pairs on the experts held (%.3f of the %d "
             "expected), the fullest expert %.2f times the mean"
             % (layer, r["differ"], r["choices"],
                (1.0 - MIN_AGREEMENT) * r["choices"], r["widest_gap"],
                NEAR_TIE, r["pairs_held"],
                r["pairs_held"] / r["pairs_expected"], r["pairs_expected"],
                r["fullest_over_mean"]))


def reference_loss(config: dict):
    """The reference on the PROGRAM's choice of experts (integer
    indices, no gradient), so that what is compared at the fixed
    tolerances is the continuous mathematics; the choices themselves
    are held to ``choices_agree``, and a batch that breaks it has no
    reference loss (nan: the comparison fails by its first limit)."""
    def loss(params, batch):
        chosen = program_choice(config, params, batch["input_ids"])
        value, routing = reference.loss_and_routing(params, batch, config,
                                                    chosen)
        agree = choices_agree(routing_report(config, chosen, routing))
        return jnp.where(agree, value, jnp.nan)
    return loss
