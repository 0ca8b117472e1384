"""The LFM2-MoE family (gated short convolutions and rotary
grouped-query attention; a dense SwiGLU or top-k routed experts a
layer): how the benchmark builds its step from the program, makes a
batch from the seed, counts the required FLOPs and calls the reference.
Sizes come from the configuration file, never from here.

The reference check and near-ties.  A token whose fourth and fifth
scores lie closer than bfloat16's rounding of the router's input
chooses differently in the program and in the float32 reference:
another function, not an error, and one whose gradients differ by more
than the comparison's tolerance (the chip read 0.08, 0.18 and 0.15 on
the three leaves with 1 to 2 % of the choices differing; PERF.md).
``reference_loss`` therefore hands the reference the program's choice
(0.03 on all three) and holds the choices themselves to
``choices_agree``.  It does so silently: a host callback would keep the
check's program out of the compile cache (50 s a run).  What the
routers did is printed once at set-up instead, by ``ingraph``'s
``init``, on the first batch of the pool: the same report of the same
two implementations, and ``hvd_moe_pairs_held{layer}``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.models import common
from benchmarks.reference import lfm2 as reference
from benchmarks.trainers.common import info
from horovod_tpu.models import lfm2
from horovod_tpu.training import (MOE_PAIRS_HELD, lfm2_step_loss,
                                  make_lfm2_train_step)


def program_config(config: dict) -> lfm2.LFM2Config:
    """``num_experts`` in the file counts the experts held; the router
    keeps the published width."""
    kinds = reference.layer_kinds(config)
    return lfm2.LFM2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        layer_types=tuple(operator for operator, _ in kinds),
        ffn_types=tuple(lfm2.DENSE if dense else lfm2.SPARSE
                        for _, dense in kinds),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        conv_L_cache=config["conv_L_cache"],
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["num_experts"],
        first_expert=config.get("first_expert", 0),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def conv_flops_per_token(config: dict) -> float:
    """One convolution operator: both projections, the taps, and the
    two gates (``B * x`` and ``C *``)."""
    hidden = config["hidden_size"]
    return 2 * hidden * 3 * hidden + 2 * hidden * hidden \
        + 2 * config["conv_L_cache"] * hidden + 2 * hidden


def attention_flops_per_token(config: dict, attended: float) -> float:
    """One attention operator: query and output projections at the
    hidden width, key and value at the key-value heads', scores and
    weighted sum of every query head over ``attended`` keys.  The norms
    and the rotation are not matrix products and are not counted."""
    hidden = config["hidden_size"]
    head_dim = hidden // config["num_attention_heads"]
    kv_width = config["num_key_value_heads"] * head_dim
    return 2 * 2 * hidden * hidden + 2 * 2 * hidden * kv_width \
        + 2 * 2 * attended * hidden


def sparse_ffn_flops_per_token(config: dict) -> float:
    """The router over all experts, and the experts held at the
    EXPECTATION under uniform routing: of a token's ``top_k`` choices
    ``held / total`` fall here (1.0 expert a token at 4 of 64 with 16
    held).  Rows of the buffer that hold no pair are not counted."""
    hidden, total = config["hidden_size"], config["published"]["num_experts"]
    expected = config["num_experts_per_tok"] * config["num_experts"] / total
    return 2 * hidden * total \
        + expected * 3 * 2 * hidden * config["moe_intermediate_size"]


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step: every layer's
    operator by its type, its dense SwiGLU (three H x I products) or
    its routed experts, causal attention at half the square, the tied
    head over the rows held at every position; recomputation and padded
    rows not counted."""
    hidden = config["hidden_size"]
    per_token = 2.0 * hidden * config["vocab_size"]
    for operator, dense in reference.layer_kinds(config):
        per_token += (conv_flops_per_token(config) if operator == lfm2.CONV
                      else attention_flops_per_token(config, seq / 2))
        per_token += (3 * 2 * hidden * config["intermediate_size"] if dense
                      else sparse_ffn_flops_per_token(config))
    return common.train_flops(per_token * batch * seq)


def host_batch(config: dict, batch: int, seq: int, rng) -> dict:
    """Tokens of the rows held: a sliced vocabulary is a smaller one."""
    return {"input_ids": rng.integers(0, config["vocab_size"], (batch, seq),
                                      dtype=np.int32)}


def optimizer(config: dict) -> optax.GradientTransformation:
    return optax.adamw(
        config["optimizer"]["learning_rate"],
        weight_decay=config["optimizer"]["weight_decay"],
        mask=lambda params: jax.tree.map(lambda p: p.ndim >= 2, params))


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_lfm2_train_step(
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
    return lfm2.LFM2LMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_lfm2_train_step``'s step itself."""
    model = lfm2.LFM2LMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        return lfm2_step_loss(model, params, batch["input_ids"])
    return loss


def system_loss(config: dict):
    train = train_loss(config)
    return lambda params, batch: train(params, batch, 0)


# What the reference check holds the program's CHOICES to, since the
# reference computes on them (module docstring).  A choice the reference
# would not have made must be a near-tie: the expert taken lies within
# NEAR_TIE under the least of the reference's own top k, in its own
# ``sigmoid + bias``.  And such choices are few: at most 1 -
# MIN_AGREEMENT of a layer's.  Each limit is the geometric mean of two
# readings on the chip at 2 x 4096 (PERF.md, PR 32): the program's in
# bfloat16, at most 0.0172 and 2.34 % (766 of 32768, the fourth sparse
# layer, over eleven batches), and those of a program whose weights keep
# three bits of mantissa, at least 0.0645 and 9.1 % (the first).
NEAR_TIE = 0.035
MIN_AGREEMENT = 0.955


def routing_of(config: dict, params, ids, program_params=None):
    """``(the program's choices, what the reference's routers saw on
    them)`` for a batch, each ``{layer: ...}``: the program as it runs,
    the reference in float32 at full precision.  ``program_params``
    gives the program other weights than the reference (a test of the
    limits rounds them)."""
    chosen = lfm2.expert_choices(
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
    first, held = config.get("first_expert", 0), config["num_experts"]
    total = config["published"]["num_experts"]
    report = {}
    for layer, took in chosen.items():
        saw = routing[layer]
        same = (took[:, :, None] == saw["own"][:, None, :]).any(-1)
        counts = lfm2.counts_by_expert(took, total)[first:first + held]
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
             "%.0e); %d pairs on the experts held (%.3f of the %d "
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
    program = program_config(config)

    def loss(params, batch):
        # The program as it runs, not at the precision the comparison
        # sets around the reference (which its kernels would refuse).
        with jax.default_matmul_precision("default"):
            chosen = lfm2.expert_choices(
                program, jax.lax.stop_gradient(params), batch["input_ids"])
        value, routing = reference.loss_and_routing(params, batch, config,
                                                    chosen)
        agree = choices_agree(routing_report(config, chosen, routing))
        return jnp.where(agree, value, jnp.nan)
    return loss
