"""The GPT family: how the benchmark builds its step from the program,
makes a batch from the seed, counts the required FLOPs and calls the
reference.  Sizes come from the configuration file, never from here."""

import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.models import common
from benchmarks.reference import gpt as reference
from horovod_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
from horovod_tpu.training import make_gpt_train_step

FFN_FACTOR = 4  # GPT-2's n_inner, which its config.json leaves null


def program_config(config: dict) -> GPTConfig:
    return GPTConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["n_embd"],
        num_layers=config["n_layer"],
        num_heads=config["n_head"],
        intermediate_size=FFN_FACTOR * config["n_embd"],
        max_position_embeddings=config["n_positions"],
        dropout=config["resid_pdrop"],
        layer_norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step: a causal
    model's attention at half the square, the tied head at every
    position, recomputation not counted."""
    h = config["n_embd"]
    per_token = common.encoder_flops_per_token(
        h, FFN_FACTOR * h, config["n_layer"], attended=seq / 2)
    per_token += 2 * h * config["vocab_size"]
    return common.train_flops(per_token * batch * seq)


def host_batch(config: dict, batch: int, seq: int, rng) -> dict:
    return {"input_ids": rng.integers(0, config["vocab_size"], (batch, seq),
                                      dtype=np.int32)}


def optimizer(config: dict) -> optax.GradientTransformation:
    return optax.adam(config["optimizer"]["learning_rate"])


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the GPT builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_gpt_train_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"])

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
    return GPTLMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_gpt_train_step``: no dropout, whatever the
    configuration says (the departure its file states)."""
    model = GPTLMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        ids = batch["input_ids"]
        return lm_loss(model.apply({"params": params}, ids), ids)
    return loss


def system_loss(config: dict):
    train = train_loss(config)
    return lambda params, batch: train(params, batch, 0)


def reference_loss(config: dict):
    return lambda params, batch: reference.loss(params, batch, config)
