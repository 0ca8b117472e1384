"""The BERT step's gradient exchange over ``dp``: which leaves the rule
takes and along which dimension, that the step which reduce-scatters,
updates a shard and all-gathers computes what the one-device step
computes, what the gauges say after a trace, and that on a ``dp`` of
one nothing of it is traced.  Whether the exchange is hidden behind the
backward pass is the chip's to say (``tests/test_chip_compile.py`` reads
the TPU compiler's text; the dp4 cell its time)."""

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.bert import (BertForMaskedLM, bert_tiny_config,
                                     mlm_loss)
from horovod_tpu.parallel import sharding
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.sharding import (DATA_AXIS_MIN_ELEMENTS,
                                           bert_partition_rules,
                                           infer_shardings,
                                           shard_over_data_axis)
from horovod_tpu.training import make_bert_batch, make_bert_pretrain_step

LEARNING_RATE = 1e-4
STEPS = 3
BATCH, SEQ = 8, 32


def _mesh(axes):
    return build_mesh(axes, jax.devices()[:math.prod(axes.values())])


@pytest.fixture
def tiny_leaves_count(monkeypatch):
    """The tiny model's matrices (2048 to 32768 elements) stand in for
    BERT-large's: the floor goes under them, its vectors stay below."""
    monkeypatch.setattr(sharding, "DATA_AXIS_MIN_ELEMENTS", 2048)


def _build(axes, **config):
    cfg = bert_tiny_config(max_position_embeddings=SEQ, dtype=jnp.float32,
                           **config)
    make_jitted, batch_sharding = make_bert_pretrain_step(
        cfg, _mesh(axes), learning_rate=LEARNING_RATE, donate=False)
    batch = jax.tree.map(lambda x: jax.device_put(x, batch_sharding),
                         make_bert_batch(BATCH, SEQ, cfg.vocab_size))
    return make_jitted(batch), batch


def _train(axes):
    (init_fn, step_fn), batch = _build(axes)
    state = init_fn(jax.random.PRNGKey(0), batch)
    for _ in range(STEPS):
        state, loss = step_fn(state, batch)
    return state, float(loss)


@pytest.fixture(scope="module")
def one_device():
    return _train({"dp": 1})


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2},
                                  {"dp": 4, "tp": 2}],
                         ids=["dp4", "dp2xtp2", "dp4xtp2"])
def test_exchanged_step_computes_the_one_device_step(
        axes, one_device, tiny_leaves_count):
    """Loss, parameters and both moments after three steps, dropout
    off, against one device.  The moments are what holds the exchange
    to a SUM of the chips' gradients (Adam's update forgives a scale);
    ``attention/key/bias`` has a gradient of zero but for rounding,
    which Adam turns into steps of the learning rate either way."""
    want, want_loss = one_device
    state, loss = _train(axes)
    assert loss == pytest.approx(want_loss, rel=1e-5)
    for (path, got), ref in zip(
            jax.tree_util.tree_leaves_with_path(state.params),
            jax.tree.leaves(want.params)):
        noise = jax.tree_util.keystr(path).endswith("['key']['bias']")
        np.testing.assert_allclose(
            got, ref, rtol=0,
            atol=(STEPS + 0.5) * LEARNING_RATE if noise else 2e-5,
            err_msg=jax.tree_util.keystr(path))
    adam, ref_adam = state.opt_state[0], want.opt_state[0]
    # mu is of the gradients' size (up to 0.09 here), nu of its square.
    for got, ref, atol in ((adam.mu, ref_adam.mu, 1e-7),
                           (adam.nu, ref_adam.nu, 1e-9)):
        for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(ref)):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=atol,
                                       err_msg=jax.tree_util.keystr(path))

    # The moments live sharded: a dp-th of every leaf the rule takes
    # (on top of what tp took), the others as the rules alone lay them.
    dp, taken = axes["dp"], 0
    for moments in (adam.mu, adam.nu):
        for moment, param in zip(jax.tree.leaves(moments),
                                 jax.tree.leaves(state.params)):
            held = math.prod(moment.sharding.shard_shape(moment.shape))
            whole = math.prod(param.sharding.shard_shape(param.shape))
            if moment.size >= 2048:
                assert held * dp == whole, (moment.shape, moment.sharding)
                taken += 1
            else:
                assert held == whole, (moment.shape, moment.sharding)
    # Two embeddings, the head's transform, six matrices a layer.
    assert taken == 2 * 15
    # ... and the parameters whole on every chip of dp.
    assert all("dp" not in leaf.sharding.spec
               for leaf in jax.tree.leaves(state.params))


def _leaf(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("axes,shape,spec,want", [
    # BERT-large's embedding: dp = 4 does not divide 30522.
    ({"dp": 4}, (30522, 1024), P(), P(None, "dp")),
    ({"dp": 4}, (1024, 4096), P(), P("dp", None)),
    ({"dp": 4}, (1024, 16, 64), P(), P("dp", None, None)),
    ({"dp": 4}, (1024,), P(), P()),              # a vector: the all-reduce
    ({"dp": 4}, (30522,), P(), P()),
    ({"dp": 4}, (255, 257), P(), P()),           # no dimension divides
    # tp's dimension is taken; the first free one that dp divides.
    ({"dp": 2, "tp": 2}, (1024, 4096), P(None, "tp"), P("dp", "tp")),
    ({"dp": 2, "tp": 2}, (16, 64, 1024), P("tp", None, None),
     P("tp", "dp", None)),
    ({"dp": 2, "tp": 2}, (30522, 1024), P("tp"), P("tp", "dp")),
], ids=["embedding", "ffn", "qkv", "vector", "mlm-bias", "odd", "tp-ffn",
        "tp-out", "tp-embedding"])
def test_rule_adds_dp_to_a_free_dimension(axes, shape, spec, want):
    mesh = _mesh(axes)
    got = shard_over_data_axis(
        {"leaf": _leaf(shape)}, {"leaf": NamedSharding(mesh, spec)}, mesh)
    assert got["leaf"] == NamedSharding(mesh, want)


def test_rule_leaves_small_leaves_and_a_dp_of_one_alone():
    assert DATA_AXIS_MIN_ELEMENTS == 65536
    mesh = _mesh({"dp": 4})
    tree = {"under": _leaf((64, 1023)), "at": _leaf((64, 1024))}
    given = {name: NamedSharding(mesh, P()) for name in tree}
    got = shard_over_data_axis(tree, given, mesh)
    assert got["under"].spec == P() and got["at"].spec == P("dp", None)
    for axes in ({"dp": 1}, {"tp": 2}):
        mesh = _mesh(axes)
        given = {name: NamedSharding(mesh, P()) for name in tree}
        assert shard_over_data_axis(tree, given, mesh) is given


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 1}], ids=["dp4", "dp1"])
def test_gauges_say_what_each_leaf_takes(axes, tiny_leaves_count):
    (init_fn, step_fn), batch = _build(axes)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), batch)
    step_fn.trace(state, batch)
    gauges = hvd.metrics_snapshot()["gauges"]
    got = {name: gauges[name] for name in ("hvd_dp_exchange_bytes",
                                           "hvd_dp_exchange_leaves")}
    sizes = [leaf.size for leaf in jax.tree.leaves(state.params)]
    large = [n for n in sizes if n >= 2048]
    if axes["dp"] == 1:     # no exchange at all
        large, sizes = [], []
    assert got == {
        "hvd_dp_exchange_bytes": {
            "form=reduce_scatter": 4.0 * sum(large),
            "form=all_reduce": 4.0 * (sum(sizes) - sum(large))},
        "hvd_dp_exchange_leaves": {
            "form=reduce_scatter": len(large),
            "form=all_reduce": len(sizes) - len(large)}}


def _step_before_the_exchange(cfg, state_sharding, batch_sharding):
    """The step as ``make_bert_pretrain_step`` wrote it before it had an
    exchange (PR 30's tree), from the package's public parts: the whole
    gradient into ``state.apply_gradients``, XLA's all-reduce."""
    model = BertForMaskedLM(cfg)

    def _loss_fn(params, batch, dropout_rng):
        logits = model.apply({"params": params}, batch["input_ids"],
                             attention_mask=batch.get("attention_mask"),
                             deterministic=False,
                             rngs={"dropout": dropout_rng})
        with jax.named_scope("loss"):
            return mlm_loss(logits, batch["labels"], batch["mask"])

    def _step(state, batch):
        dropout_rng = jax.random.fold_in(
            jax.random.key(0, impl="rbg"), state.step)
        loss, grads = jax.value_and_grad(_loss_fn)(
            state.params, batch, dropout_rng)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads=grads)
        return new_state, loss

    return jax.jit(
        _step,
        in_shardings=(state_sharding,
                      {name: batch_sharding
                       for name in ("input_ids", "labels", "mask")}),
        out_shardings=(state_sharding,
                       NamedSharding(batch_sharding.mesh, P())))


def test_a_dp_of_one_lowers_to_the_step_before_the_exchange(
        tiny_leaves_count):
    """The one-chip cells run the program they ran before the exchange
    was written: the ``{"dp": 1}`` step lowers to the text of the step
    as it was written then, source lines aside; on ``dp = 4`` the two
    differ, so the comparison can see an exchange.  (On the chip the
    one-chip cell was served the executable the parent had compiled:
    PERF.md, PR 31, call 3.)"""
    def lowered(axes):
        (init_fn, step_fn), batch = _build(axes, hidden_dropout=0.1,
                                           attention_dropout=0.1)
        state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), batch)
        cfg = bert_tiny_config(
            max_position_embeddings=SEQ, dtype=jnp.float32,
            hidden_dropout=0.1, attention_dropout=0.1)
        before = _step_before_the_exchange(
            cfg, infer_shardings(state, _mesh(axes),
                                 bert_partition_rules(tp=None)),
            batch["input_ids"].sharding)
        # Shapes alone: each step lays the state out by its own
        # ``in_shardings`` (the moments' differ on dp = 4).
        state = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), state)
        return [re.sub(r"loc\([^)]*\)", "", fn.lower(state, batch).as_text())
                for fn in (step_fn, before)]

    now, before = lowered({"dp": 1})
    assert now == before
    now, before = lowered({"dp": 4})
    assert now != before
