"""The Granite hybrid family (Mamba-2 layers beside grouped-query
attention): how the benchmark builds its step from the program, makes a
batch from the seed, counts the required FLOPs and calls the reference.
Sizes come from the configuration file, never from here."""

import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.models import common
from benchmarks.reference import granite as reference
from horovod_tpu.models.granite import GraniteConfig, GraniteLMHeadModel
from horovod_tpu.training import granite_step_loss, make_granite_train_step


def layer_kinds(config: dict):
    """The kinds of the layers held: the first ``num_hidden_layers`` of
    the published ``layer_types``."""
    return tuple(config["layer_types"][:config["num_hidden_layers"]])


def program_config(config: dict) -> GraniteConfig:
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if inner != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is %d, mamba_expand x "
                         "hidden_size %d" % (inner, config["mamba_expand"]
                                             * config["hidden_size"]))
    if config["mamba_n_groups"] != 1 or config["num_local_experts"] != 0:
        raise ValueError("the program's Granite has one group of B and C "
                         "and no routed expert")
    return GraniteConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["shared_intermediate_size"],
        layer_types=layer_kinds(config),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def mamba_flops_per_token(config: dict) -> float:
    """Forward multiply-adds times two, per token, of one Mamba-2 mixer:
    both projections, the depthwise convolution, and the recurrence in
    its chunked form at the published chunk length: within a chunk the
    ``C . B`` scores (one group, shared by the heads) and the weighted
    sum over the inputs, both at half the chunk (it is causal); the
    state a chunk leaves and the entering state's output."""
    hidden, state = config["hidden_size"], config["mamba_d_state"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_width = inner + 2 * state
    chunk = config["mamba_chunk_size"]
    projections = 2 * hidden * (inner + conv_width + config["mamba_n_heads"]) \
        + 2 * inner * hidden
    conv = 2 * config["mamba_d_conv"] * conv_width
    within = 2 * (chunk / 2) * state + 2 * (chunk / 2) * inner
    states = 2 * 2 * inner * state
    return projections + conv + within + states


def attention_flops_per_token(config: dict, attended: float) -> float:
    """One grouped-query attention mixer: query and output projections
    at the hidden width, key and value at the key-value heads', scores
    and weighted sum of every query head over ``attended`` keys."""
    hidden = config["hidden_size"]
    head_dim = hidden // config["num_attention_heads"]
    kv_width = config["num_key_value_heads"] * head_dim
    return 2 * 2 * hidden * hidden + 2 * 2 * hidden * kv_width \
        + 2 * 2 * attended * hidden


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step: every layer's
    mixer by its kind and its gated MLP (three H x I products), causal
    attention at half the square, the tied head over the rows held at
    every position, recomputation not counted."""
    hidden = config["hidden_size"]
    kinds = layer_kinds(config)
    per_token = len(kinds) * 3 * 2 * hidden * config["shared_intermediate_size"]
    per_token += kinds.count("mamba") * mamba_flops_per_token(config)
    per_token += kinds.count("attention") * attention_flops_per_token(
        config, attended=seq / 2)
    per_token += 2 * hidden * config["vocab_size"]
    return common.train_flops(per_token * batch * seq)


def host_batch(config: dict, batch: int, seq: int, rng) -> dict:
    """Tokens of the rows held: a sliced vocabulary is a smaller one."""
    return {"input_ids": rng.integers(0, config["vocab_size"], (batch, seq),
                                      dtype=np.int32)}


def optimizer(config: dict) -> optax.GradientTransformation:
    return optax.adamw(config["optimizer"]["learning_rate"],
                       weight_decay=config["optimizer"]["weight_decay"])


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_granite_train_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"],
        weight_decay=config["optimizer"]["weight_decay"])

    def init(key, batch):
        return init_fn(key, batch["input_ids"])

    def step(state, batch):
        params, opt_state, loss = step_fn(*state, batch["input_ids"])
        return (params, opt_state), loss

    def hlo_text(state, batch):
        return step_fn.lower(*state, batch["input_ids"]).compile().as_text()

    return common.InGraph(init, step, lambda state: state[0], hlo_text,
                          batch_sharding)


def init_params(config: dict, key, batch):
    return GraniteLMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_granite_train_step``'s step itself: the stack,
    then the tied and scaled head over chunks of the sequence."""
    model = GraniteLMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        return granite_step_loss(model, params, batch["input_ids"])
    return loss


def system_loss(config: dict):
    train = train_loss(config)
    return lambda params, batch: train(params, batch, 0)


def reference_loss(config: dict):
    return lambda params, batch: reference.loss(params, batch, config)
