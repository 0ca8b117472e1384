"""The AFMoE family (window and full grouped-query attention in one
stack, each head gated; a dense SwiGLU or top-k routed experts beside a
shared expert; a selection bias the step moves): how the benchmark
builds its step from the program, makes a batch from the seed, counts
the required FLOPs, and the flash kernels' on the band and on the
triangle, and calls the reference.  Sizes come from the configuration
file, never from here.

The reference check and near-ties: as for the DeepSeek-V3 family
(``benchmarks/models/deepseek_v3.py`` has the reasons), but that BOTH
sides compute on the float32 reference's own choice
(``reference_routing`` has the reason) and the program's choice as it
runs is held to it, silently, by ``choices_agree`` on the system's side;
``init`` prints what the routers did on the first batch of the pool.
The moved bias is
held to the reference's twice.  The RULE on a real batch's choice
(``biases_agree``): both sides count ONE choice, so the counts and the
signs are the same whole numbers, and a reference bias further from the
program's than a rounding of the last bit (``BIAS_ATOL``) leaves the
batch no reference loss.  And the TIMED step's own program
(``step_moves_forced_bias``, once, in ``init``): under a bias that
forces every token's choice the step's ``expert_bias`` leaves must lie
where the reference's rule puts them, or no step of the run has a loss.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models import common
from benchmarks.models.lfm2 import host_batch, optimizer  # noqa: F401  the
#   same batches (tokens of the rows held) and the same AdamW
from benchmarks.reference import afmoe as reference
from benchmarks.reference import common as common_reference
from benchmarks.trainers.common import info
from horovod_tpu.models import afmoe
from horovod_tpu.models.layers import counts_by_expert
from horovod_tpu.parallel import moe
from horovod_tpu.training import (MOE_LOAD_MAX_OVER_MEAN, MOE_PAIRS_HELD,
                                  afmoe_step_loss, make_afmoe_train_step,
                                  move_selection_bias)

def program_config(config: dict) -> afmoe.AfmoeConfig:
    """``num_experts`` in the file counts the experts held; the router
    keeps the published width."""
    if config["rope_scaling"] is not None:
        raise ValueError("the program has no rotary scaling")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's model has a head of its own")
    kinds = reference.layer_kinds(config)
    return afmoe.AfmoeConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_dense_layers=config["num_dense_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=tuple(kind for kind, _ in kinds),
        sliding_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["num_experts"],
        first_expert=config.get("first_expert", 0),
        num_shared_experts=config["num_shared_experts"],
        route_norm=config["route_norm"],
        route_scale=float(config["route_scale"]),
        load_balance_coeff=float(config["load_balance_coeff"]),
        mup_enabled=config["mup_enabled"],
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def pairs_attended(config: dict, kind: str, seq: int) -> float:
    """Pairs of query and key a head's scores need over one sequence:
    the band of a window layer (``W (W + 1) / 2`` under the first
    ``W`` queries, ``W`` a query after them), half the square of a full
    one, as the other families count a causal square."""
    if kind != afmoe.SLIDING:
        return seq * seq / 2
    window = min(config["sliding_window"], seq)
    return window * (window + 1) / 2 + (seq - window) * window


def attention_flops_per_token(config: dict, kind: str, seq: int) -> float:
    """One attention operator: the query, gate and output projections at
    the query heads' width, key and value at the key-value heads', and
    every query head's scores and weighted sum over the keys it sees
    (``pairs_attended``).  The norms, the rotation and the gate's
    product are not matrix products and are not counted."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    d = config["head_dim"]
    projections = 3 * hidden * heads * d \
        + hidden * config["num_key_value_heads"] * 2 * d
    return 2 * projections \
        + 2 * pairs_attended(config, kind, seq) / seq * heads * 2 * d


def sparse_ffn_flops_per_token(config: dict) -> float:
    """The router over all experts, the shared expert at every token,
    and the routed experts held at the EXPECTATION under uniform
    routing: of a token's ``top_k`` choices ``held / total`` fall here
    (one expert a token at 8 of 128 with 16 held).  Rows of the buffer
    that hold no pair are not counted."""
    hidden, total = config["hidden_size"], config["published"]["num_experts"]
    width = config["moe_intermediate_size"]
    expected = config["num_experts_per_tok"] * config["num_experts"] / total
    return 2 * hidden * total \
        + (config["num_shared_experts"] + expected) * 3 * 2 * hidden * width


def flops_per_token(config: dict, seq: int) -> float:
    """Required forward FLOPs a token at sequences of ``seq``."""
    hidden = config["hidden_size"]
    per_token = 2.0 * hidden * config["vocab_size"]
    for kind, dense in reference.layer_kinds(config):
        per_token += attention_flops_per_token(config, kind, seq)
        per_token += (3 * 2 * hidden * config["intermediate_size"] if dense
                      else sparse_ffn_flops_per_token(config))
    return per_token


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step: attention by
    its layer's kind, a window layer at the BAND (a kernel that masks
    the band and walks the triangle does work that is not counted);
    the dense SwiGLU (three H x I products) or the sparse feed-forward;
    the head over the rows held at every position; recomputation,
    padded rows and masked scores not counted."""
    return common.train_flops(flops_per_token(config, seq) * batch * seq)


def flash_kernel_work(config: dict, batch: int, seq: int) -> dict:
    """``{kernel: (FLOPs, HBM bytes)}`` of one call of each flash kernel
    on ``[batch, seq, heads, head_dim]`` (one layer, one pass): the
    full layer's at half the square, the window layers' at the band:
    what the algorithm needs, not what the tiles on an edge spend.  The
    forward makes scores and the weighted sum, the fused backward
    scores, ``dV = P^T dO``, ``dP = dO V^T``, ``dK = dS^T Q`` and ``dQ =
    dS K``.  Bytes: every operand read once and every result written
    once (the keys and values as the kernels take them, laid out a
    query head), the row statistics in float32."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    itemsize = np.dtype(config["compute_dtype"]).itemsize
    rows = batch * seq * heads
    wide, stat = rows * d * itemsize, rows * 4
    work = {}
    for suffix, kind in (("", afmoe.FULL), ("_window", afmoe.SLIDING)):
        product = 2.0 * batch * heads * pairs_attended(config, kind, seq) * d
        work["hvd_flash_fwd" + suffix] = (2 * product, 4 * wide + stat)
        work["hvd_flash_bwd" + suffix] = (5 * product, 8 * wide + 2 * stat)
    return work


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_afmoe_train_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"],
        weight_decay=config["optimizer"]["weight_decay"])

    report = jax.jit(lambda params, ids: routing_report(
        config, *routing_of(config, params, ids)))
    moved = {}

    def init(key, batch):
        # The state is made twice from one key: the timed step consumes
        # the first, under a bias that forces its choice.
        moved["as_the_rule"] = step_moves_forced_bias(
            config, step_fn, init_fn(key, batch["input_ids"]),
            batch["input_ids"])
        state = init_fn(key, batch["input_ids"])
        say(jax.device_get(report(state[0], batch["input_ids"])))
        return state

    def step(state, batch):
        params, opt_state, loss = step_fn(*state, batch["input_ids"])
        # A program that does not move the bias by the rule has no loss:
        # ``correct`` counts every step of it failed.
        return (params, opt_state), \
            loss if moved["as_the_rule"] else loss * jnp.nan

    def hlo_text(state, batch):
        return step_fn.lower(*state, batch["input_ids"]).compile().as_text()

    return common.InGraph(init, step, lambda state: state[0], hlo_text,
                          batch_sharding)


def init_params(config: dict, key, batch):
    return afmoe.AfmoeLMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_afmoe_train_step``'s step itself."""
    model = afmoe.AfmoeLMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        return afmoe_step_loss(model, params, batch["input_ids"])
    return loss


def program_choice(config: dict, params, ids):
    """The program's choice of experts on ``ids`` as it runs, in
    bfloat16 (``{layer: [T, top_k]}``, no gradient), from a forward pass
    that a barrier keeps apart from the rest of the program it is asked
    in: what ``init`` reports, and what the check holds to the
    reference's routers (``choices_agree``)."""
    params, ids = jax.lax.optimization_barrier(
        (jax.lax.stop_gradient(params), ids))
    # The program's own precision, not the one the comparison sets
    # around the reference (which its kernels would refuse).
    with jax.default_matmul_precision("default"):
        chosen = afmoe.expert_choices(program_config(config), params, ids)
    return jax.lax.optimization_barrier(chosen)


def reference_routing(config: dict, params, ids, chosen=None):
    """What the float32 reference's routers see and choose (``{layer:
    {"own": [T, top_k], "biased": [T, E]}}``, no gradient) on the
    stream that ``chosen`` makes (``{layer: [T, top_k]}``: the experts
    taken, layer by layer), or on the reference's OWN stream: BOTH
    sides of the comparison compute on that ``own``.  The comparison's
    two sides are two compiled programs, and
    two compiles of one low-precision forward pass do not decide every
    near-tie alike: of the program's bfloat16 pass behind a barrier
    (enough at kanana's 2 x 8192) the chip read 0.11 and 0.20 on a
    router's leaf here; of the same pass with every value between its
    operations kept in float32, its products still rounding their
    operands, 0.04 to 0.12 on the routers by depth and 0.02 to 0.33 on
    the experts of one stack, an expert either clean or not, where
    attention's leaves and the shared expert of the same layer read
    0.02 in every variant, kernels or none, bfloat16 or float32, and any
    two programs lay as far from each other as from the reference
    (PERF.md, PR 45).  A product at full precision differs between two
    compiles by the order of a float32 sum."""
    params, ids = jax.lax.optimization_barrier(
        (jax.lax.stop_gradient(params), ids))
    with jax.default_matmul_precision("highest"):
        _, _, routing = reference.hidden_and_routing(
            params, {"input_ids": ids}, config, chosen)
    return jax.lax.optimization_barrier(routing)


def _a_sequence_at_a_time(config: dict, loss_of_one, recompute: bool):
    """``loss(params, batch)``, the mean over the batch's sequences of
    ``loss_of_one(params, ids [1, S], saw)``, one sequence after
    another: a step of the timed shape (one sequence a chip) a trip,
    and one sequence's activations alive; with ``recompute`` a trip
    keeps nothing for the backward pass and is made again there.  The
    check's programs are loaded beside the whole training state.

    ``saw`` is ``reference_routing`` on the trip's sequence, made ONCE
    a trip and OUTSIDE what is recomputed (kept), so that the value and
    the gradient are of one choice."""
    def loss(params, batch):
        def trip(params, ids, saw):
            return loss_of_one(params, ids[None], saw)

        def one(ids):
            saw = reference_routing(config, params, ids[None])
            return (jax.checkpoint(trip) if recompute else trip)(
                params, ids, saw)
        return jax.lax.map(one, batch["input_ids"]).mean()
    return loss


def _own(saw: dict) -> dict:
    return {layer: s["own"] for layer, s in saw.items()}


def system_loss(config: dict):
    """The step's loss on the reference's own choice, and no loss (nan)
    where the program's choice as it runs is another in more than a few
    near-ties (``choices_agree``); keeping across ``remat`` the choice
    it is handed and nothing else:
    what a trip keeps waits, stacked over the sequences, for the
    backward pass, beside 8.5 GB of training state (a sandbox compile
    for a described v5e: 7.66 GB of temporaries with the kernels'
    output kept too, 6.73 without; the forward kernel then runs again
    in the backward pass, which costs the check time and no memory)."""
    model = afmoe.AfmoeLMHeadModel(program_config(config),
                                   remat_names=(moe.CHOICE_NAME,))

    def of_one(params, ids, saw):
        value = afmoe_step_loss(model, params, ids, _own(saw))
        agree = choices_agree(routing_report(
            config, *routing_of(config, params, ids)))
        return jnp.where(agree, value, jnp.nan)
    return _a_sequence_at_a_time(config, of_one, recompute=False)


# What the reference check holds the program's CHOICES to, since the
# reference computes on them.  A choice the reference would not have
# made must be a near-tie: the expert taken lies within NEAR_TIE under
# the least of the reference's own top k, in its own ``sigmoid + bias``.
# And such choices are few: at most 1 - MIN_AGREEMENT of a layer's.
# Each limit lies between two readings on the chip at 1 x 16384 and the
# published widths (PERF.md, PR 45; two seeds, four layers each): the
# program in bfloat16 takes 0.77 to 1.16 % of a layer's choices that
# the reference's routers would not, the widest gap 0.0052 to 0.0082;
# a program whose weights keep three bits of mantissa 6.8 to 8.9 %, the
# widest gap 0.058 to 0.077.  The values are kanana's, the same kind of
# router.
NEAR_TIE = 0.034
MIN_AGREEMENT = 0.955


def routing_of(config: dict, params, ids, program_params=None):
    """``(the program's choices as it runs, what the reference's routers
    saw on them)`` for a batch, each ``{layer: ...}``: a layer's router
    reads the stream that the program's choices made in the layers
    before it, so that what it tells of a choice is that layer's own
    near-tie, and not an earlier one's carried on.  ``program_params``
    gives the program other weights than the reference (a test of the
    limits rounds them)."""
    chosen = program_choice(
        config, params if program_params is None else program_params, ids)
    return chosen, reference_routing(config, params, ids, chosen)


def routing_report(config: dict, chosen: dict, routing: dict) -> dict:
    """By sparse layer, from the program's choices (``{layer: [T,
    top_k]}``) and what the reference's routers saw on them: how many
    the reference would not have made and the widest gap among those, the
    pairs that fell on the experts held beside the expectation that
    ``flops_per_step`` counts, the fullest expert's load over the mean
    load of those held, and over the mean of ALL experts (what the
    moved bias pulls towards 1)."""
    first, held = config.get("first_expert", 0), config["num_experts"]
    total = config["published"]["num_experts"]
    report = {}
    for layer, took in chosen.items():
        saw = routing[layer]
        same = (took[:, :, None] == saw["own"][:, None, :]).any(-1)
        loads = counts_by_expert(took, total)
        counts = loads[first:first + held]
        report[layer] = {"choices": took.size,
                         "differ": took.size - same.sum(),
                         "widest_gap": reference.gap_under_own(
                             saw, took).max(),
                         "pairs_held": counts.sum(),
                         "pairs_expected": took.size * held / total,
                         "fullest_over_mean": counts.max() / counts.mean(),
                         "load_max_over_mean": loads.max() / loads.mean()}
    return report


def choices_agree(report: dict):
    """Every layer's choices within the two limits above."""
    return jnp.all(jnp.stack(
        [(r["differ"] <= (1.0 - MIN_AGREEMENT) * r["choices"])
         & (r["widest_gap"] <= NEAR_TIE) for r in report.values()]))


# How far the program's moved bias may lie from the reference's.  Both
# count one choice, so both add the same whole-number signs; what is
# left is whether a compiler rounds ``bias + step * x`` once (fused) or
# twice: a unit in the last place of a bias of a few thousandths, 1e-10.
# The least the rule can move a bias otherwise is one sign's share of
# the recentring, ``load_balance_coeff / experts`` = 7.8e-6.
BIAS_ATOL = 1e-8


def biases_agree(config: dict, params, chosen: dict):
    """The selection bias of every sparse layer after one update by
    ``chosen``: the program's rule (``move_selection_bias``) and the
    reference's (``bias_after_update``) within ``BIAS_ATOL``."""
    moved = move_selection_bias(params, chosen,
                                float(config["load_balance_coeff"]))
    bias = lambda tree, layer: tree["layer_%d" % layer]["moe"]["expert_bias"]
    return jnp.all(jnp.stack([
        jnp.all(jnp.abs(bias(moved, layer) - reference.bias_after_update(
            bias(params, layer).astype(jnp.float32), took, config))
            <= BIAS_ATOL)
        for layer, took in chosen.items()]))


# A selection bias that decides the choice whatever the scores: a
# sigmoid lies inside (0, 1), so the experts that carry it are every
# token's top k under any rounding, in any compile.  The moved bias is
# then compared to two units in the last place of the bias carried
# (float32 at 2.0: 2.4e-7); the least the rule moves a bias is 7.8e-6.
FORCING_BIAS = 2.0
FORCED_BIAS_ATOL = 5e-7


def forced_choice(config: dict, layer: int):
    """The ``top_k`` experts, of ALL the router's, that the forcing
    bias of sparse layer ``layer`` makes every token choose: evenly
    spaced from expert ``layer`` on, so others in every layer, and (16
    of 128 held, top 8) one of them held here: the expected load."""
    total, top_k = (config["published"]["num_experts"],
                    config["num_experts_per_tok"])
    return (total // top_k * np.arange(top_k) + layer) % total


def step_moves_forced_bias(config: dict, step_fn, state, ids) -> bool:
    """Whether the TIMED step's own program moves the selection bias as
    the reference's rule does (``reference.bias_after_update``): one
    call of ``step_fn`` on ``state`` (consumed) with every sparse
    layer's bias replaced by one that forces its choice
    (``forced_choice``), so that the choices the program counted are
    known without asking it, and its ``expert_bias`` leaves afterwards
    beside the reference's: the counts over ALL experts and every one of
    a token's choices, the signs, the coefficient, the recentring, after
    an optimizer that leaves the bias alone.  ``biases_agree`` holds the
    rule to the reference on a real batch's choice; this holds the
    program that is timed."""
    params, opt_state = state
    name = "layer_%d/moe/expert_bias"
    before = {}
    for layer, (_, dense) in enumerate(reference.layer_kinds(config)):
        if not dense:
            before[layer] = np.zeros(
                config["published"]["num_experts"], np.float32)
            before[layer][forced_choice(config, layer)] = FORCING_BIAS
    # The step is handed copies on the device, which it consumes.
    params, _, loss = step_fn(
        common_reference.with_leaves(params, {
            name % layer: jax.device_put(
                bias, common_reference.get_leaf(params, name % layer).sharding)
            for layer, bias in before.items()}),
        opt_state, ids)
    furthest = max(
        float(jnp.abs(
            common_reference.get_leaf(params, name % layer)
            - reference.bias_after_update(
                bias, jnp.broadcast_to(
                    forced_choice(config, layer),
                    (ids.size, config["num_experts_per_tok"])), config)).max())
        for layer, bias in before.items())
    ok = bool(np.isfinite(float(loss))) and furthest <= FORCED_BIAS_ATOL
    info("the timed step under a bias that forces its choice: its moved "
         "bias lies %.1e from the reference's rule at most (limit %.1e), "
         "its loss %.4f: %s" % (furthest, FORCED_BIAS_ATOL, float(loss),
                                "ok" if ok else "FAILED"))
    return ok


def say(report: dict):
    """The report's lines, and the gauges."""
    for layer, r in sorted(report.items()):
        r = {k: float(v) for k, v in r.items()}
        MOE_PAIRS_HELD.set(r["pairs_held"], layer=str(layer))
        MOE_LOAD_MAX_OVER_MEAN.set(r["load_max_over_mean"], layer=str(layer))
        info("sparse layer %d: %d of %d choices are not the float32 "
             "reference's own (at most %d may), the widest gap %.2e (limit "
             "%.1e); %d pairs on the experts held (%.3f of the %d "
             "expected), the fullest expert %.2f times the mean of those "
             "held, %.2f times the mean of all"
             % (layer, r["differ"], r["choices"],
                (1.0 - MIN_AGREEMENT) * r["choices"], r["widest_gap"],
                NEAR_TIE, r["pairs_held"],
                r["pairs_held"] / r["pairs_expected"], r["pairs_expected"],
                r["fullest_over_mean"], r["load_max_over_mean"]))


def reference_loss(config: dict):
    """The reference on its own choice (integer indices, no gradient),
    which the system computes on too, so that what is compared at the
    fixed tolerances is the continuous mathematics; the program's
    choice as it runs is held to it in ``system_loss``, and the bias
    the choice moves to ``biases_agree``: a sequence that breaks it has
    no reference loss (nan: the comparison fails by its first limit)."""
    def of_one(params, ids, saw):
        chosen = _own(saw)
        value = reference.loss(params, {"input_ids": ids}, config, chosen)
        agree = biases_agree(config, jax.lax.stop_gradient(params), chosen)
        return jnp.where(agree, value, jnp.nan)
    # Recomputed: what the reference's layers keep in float32 would
    # otherwise wait for the backward pass beside the training state.
    return _a_sequence_at_a_time(config, of_one, recompute=True)
