"""The BERT family: how the benchmark builds its step from the program,
makes a batch from the seed, counts the required FLOPs and calls the
reference.  Sizes come from the configuration file, never from here."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.models import common
from benchmarks.reference import bert as reference
from horovod_tpu.models.bert import BertConfig, BertForMaskedLM, mlm_loss
from horovod_tpu.training import make_bert_pretrain_step

MASKED_SHARE = 0.15


def program_config(config: dict) -> BertConfig:
    return BertConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout=config["hidden_dropout_prob"],
        attention_dropout=config["attention_probs_dropout_prob"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step.  The head (its
    H x H transform and the H x vocabulary product) is counted at every
    position, as the objective the program trains computes it."""
    h, v = config["hidden_size"], config["vocab_size"]
    per_token = common.encoder_flops_per_token(
        h, config["intermediate_size"], config["num_hidden_layers"],
        attended=seq)
    per_token += 2 * h * h + 2 * h * v
    return common.train_flops(per_token * batch * seq)


def host_batch(config: dict, batch: int, seq: int, rng) -> dict:
    v = config["vocab_size"]
    return {
        "input_ids": rng.integers(0, v, (batch, seq), dtype=np.int32),
        "labels": rng.integers(0, v, (batch, seq), dtype=np.int32),
        "mask": (rng.random((batch, seq)) < MASKED_SHARE).astype(np.int32),
    }


def optimizer(config: dict) -> optax.GradientTransformation:
    opt = config["optimizer"]
    return optax.adamw(opt["learning_rate"],
                       weight_decay=opt["weight_decay"])


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    # The dropout seed stays the program's default: it is a constant of
    # the compiled step, and a step that changed with --seed would
    # never be found in the compile cache.
    make_jitted, batch_sharding = make_bert_pretrain_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"])
    placed = jax.tree.map(lambda a: jax.device_put(a, batch_sharding),
                          example_batch)
    init_fn, step_fn = make_jitted(placed)
    return common.InGraph(
        init_fn, step_fn, lambda state: state.params,
        lambda state, batch: step_fn.lower(state, batch).compile().as_text(),
        batch_sharding)


def init_params(config: dict, key, batch):
    model = BertForMaskedLM(program_config(config))
    return model.init(key, batch["input_ids"], deterministic=True)["params"]


def train_loss(config: dict):
    """The loss of ``training.py``'s step: dropout as the configuration
    says, its rng folded from the step (and from a constant seed, as
    there)."""
    pc = program_config(config)
    model = BertForMaskedLM(pc)
    deterministic = pc.hidden_dropout == 0.0 and pc.attention_dropout == 0.0

    def loss(params, batch, step):
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        logits = model.apply(
            {"params": params}, batch["input_ids"],
            deterministic=deterministic,
            rngs=None if deterministic else {"dropout": rng})
        return mlm_loss(logits, batch["labels"], batch["mask"])
    return loss


def system_loss(config: dict):
    """The system's forward and loss, deterministic, in the cell's
    compute type: what the reference is compared with."""
    model = BertForMaskedLM(program_config(config))

    def loss(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"],
                             deterministic=True)
        return mlm_loss(logits, batch["labels"], batch["mask"])
    return loss


def reference_loss(config: dict):
    return lambda params, batch: reference.loss(params, batch, config)
