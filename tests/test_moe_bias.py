"""The rule that moves a sparse layer's selection bias
(``parallel/moe.py`` ``moved_bias``, ``training.py``
``move_selection_bias``): its signs, its recentring, what a tie does,
the counts of a batch that lies over two devices, and the families that
have no rule."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from horovod_tpu import training
from horovod_tpu.models import afmoe, deepseek_v3, lfm2
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_mesh


@pytest.mark.parametrize("counts,signs", [
    ([10, 0, 5, 5], [-1, 1, 0, 0]),        # over, under, two at the mean
    ([4, 4, 4, 4], [0, 0, 0, 0]),          # a tie: nothing moves
    ([0, 0, 0, 40], [1, 1, 1, -1]),        # one expert takes all
    ([7, 1, 1, 1, 1, 1, 1, 3], [-1, 1, 1, 1, 1, 1, 1, -1])],
    ids=["over-under-mean", "tie", "collapse", "eight"])
def test_the_rule_by_hand(counts, signs):
    """``d_e = step * sign(mean(n) - n_e)``, ``b_e += d_e - mean(d)``:
    an expert over the mean load is lowered, one under it raised, one AT
    it left to the recentring alone, which keeps the bias's sum where it
    was."""
    step = 1e-3
    start = jnp.asarray(np.linspace(-0.01, 0.01, len(counts)), jnp.float32)
    got = np.asarray(moe.moved_bias(start, jnp.asarray(counts), step))
    d = step * np.asarray(signs, np.float64)
    want = np.asarray(start, np.float64) + d - d.mean()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9)
    assert float(got.sum()) == pytest.approx(float(start.sum()), abs=1e-8)
    if not any(signs):
        assert (got == np.asarray(start)).all()


def test_the_bias_chooses_and_the_rule_evens_the_loads():
    """What the rule is for: tokens whose scores favour one expert
    choose it less, step by step, once its bias falls."""
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    # one feature that every token has, and expert 0 alone reads
    x, router = x.at[:, 0].set(2.0), router.at[0].set(0.0).at[0, 0].set(1.0)
    bias = jnp.zeros(8)

    def spread(bias):
        chosen = moe.sigmoid_top_k(x, router, bias, 2).chosen
        counts = jnp.bincount(chosen.reshape(-1), length=8)
        return counts, float(counts.max() / counts.mean())
    _, before = spread(bias)
    for _ in range(200):
        bias = moe.moved_bias(bias, spread(bias)[0], 1e-2)
    _, after = spread(bias)
    assert before > 1.5 and after < 1.2
    assert abs(float(bias.sum())) < 1e-5


@pytest.mark.parametrize("axes", [{"dp": 1}, {"dp": 2}], ids=["dp1", "dp2"])
def test_the_steps_counts_are_the_whole_batchs(axes):
    """On a data axis of two each device holds half the batch, and the
    step's counts are of ALL its tokens: the bias after a step is the
    rule on the whole batch's choices, whichever mesh ran it."""
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = training.make_afmoe_train_step(
        cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                             cfg.vocab_size)
    params, opt_state = init_fn(jax.random.PRNGKey(1),
                                jax.device_put(ids, batch_sharding))
    chosen = afmoe.expert_choices(cfg, jax.device_get(params), ids)
    new, _, _ = step_fn(params, opt_state,
                        jax.device_put(ids, batch_sharding))
    for layer, took in chosen.items():
        counts = jnp.bincount(took.reshape(-1), length=cfg.num_experts)
        assert int(counts.sum()) == 4 * 64 * cfg.num_experts_per_tok
        want = moe.moved_bias(jnp.zeros(cfg.num_experts), counts,
                              cfg.load_balance_coeff)
        got = new["layer_%d" % layer]["moe"]["expert_bias"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-9)
        # half a batch alone would have counted otherwise
        half = jnp.bincount(took[:128].reshape(-1), length=cfg.num_experts)
        assert (np.sign(np.asarray(half.mean() - half))
                != np.sign(np.asarray(counts.mean() - counts))).any() \
            or layer != 1


def test_a_coefficient_of_zero_is_no_rule():
    row = training._afmoe_family()
    assert row.bias_step(afmoe.afmoe_tiny_config()) == 1e-3
    assert row.bias_step(afmoe.afmoe_tiny_config(
        load_balance_coeff=0.0)) is None


@pytest.mark.parametrize("family,make,tiny", [
    ("lfm2", training.make_lfm2_train_step, lfm2.lfm2_tiny_config),
    ("deepseek_v3", training.make_deepseek_v3_train_step,
     deepseek_v3.deepseek_v3_tiny_config)])
def test_a_family_without_a_rule_keeps_its_bias_and_counts_nothing(
        family, make, tiny):
    """kanana's and LFM2's rows name no rule: their step's program holds
    no bias update (no such scope, no count of the choices) and their
    bias stays the zeros it was initialised to."""
    row = getattr(training, "_%s_family" % family)()
    cfg = tiny(dtype=jnp.float32)
    assert row.bias_step(cfg) is None
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    init_fn, step_fn, batch_sharding = make(cfg, mesh)
    ids = jax.device_put(jax.random.randint(
        jax.random.PRNGKey(0), (2, 64), 0, cfg.vocab_size), batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    text = step_fn.lower(params, opt_state, ids).as_text(debug_info=True)
    assert "bias_update" not in text
    new, _, _ = step_fn(params, opt_state, ids)
    biases = [layer["moe"]["expert_bias"] for name, layer in new.items()
              if name.startswith("layer_") and "moe" in layer]
    assert biases and all((np.asarray(b) == 0).all() for b in biases)
    # and AFMoE's holds one
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32)
    init_fn, step_fn, batch_sharding = training.make_afmoe_train_step(
        cfg, mesh)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(1), ids)
    assert "bias_update" in step_fn.lower(*state, ids).as_text(
        debug_info=True)
