"""The DeepSeek-V3 family in the program: the step's loss and every
gradient against the plain reference, the rotation of pairs, the
experts' shares with the shared expert counted once, latent attention
through the kernels against its einsums, the step on a dp x tp mesh and
its gauges."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import horovod_tpu as hvd
from benchmarks.reference import deepseek_v3 as reference
from horovod_tpu.models import deepseek_v3
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.sharding import (deepseek_v3_partition_rules,
                                           infer_shardings)
from horovod_tpu.training import (_tied_head_loss, deepseek_v3_step_loss,
                                  make_deepseek_v3_train_step)


def file_config(cfg: deepseek_v3.DeepseekV3Config) -> dict:
    """``cfg`` under the keys of a configuration file, which is what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_expert=cfg.first_expert)


def tiny(**kw):
    """One dense layer and two sparse; 4 heads of 24 = 16 + 8 and 16
    over a latent of 32; 8 experts, 4 held, top 2, scale 2.448."""
    cfg = deepseek_v3.deepseek_v3_tiny_config(dtype=jnp.float32, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                             cfg.vocab_size)
    model = deepseek_v3.DeepseekV3LMHeadModel(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1), ids)["params"], ids


@pytest.mark.parametrize("first_expert", [0, 4], ids=["first0", "first4"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_and_every_gradient_equal_the_reference(remat,
                                                          first_expert):
    """The step's own loss (latent attention with the one rotated key
    read as it is, sort-and-gather dispatch, grouped products, the
    shared expert, an untied head over chunks of the sequence) against
    the reference, which expands every head's key, has no dispatch and
    rotates the pairs in place.  In float32 both choose alike."""
    cfg, model, params, ids = tiny(remat=remat, first_expert=first_expert)
    assert cfg.routed_scaling_factor != 1.0 and cfg.n_shared_experts == 2
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: deepseek_v3_step_loss(model, p, ids)))(params)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, {"input_ids": ids},
                                     file_config(cfg))))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got, flat_want = jax.tree.leaves(got_g), jax.tree.leaves(want_g)
    # three layers of 5 attention leaves and 2 norms; a dense SwiGLU of
    # 3; two sparse layers of 5 + 3; embedding, head, final norm
    assert len(flat_got) == len(flat_want) == 3 * 7 + 3 + 2 * 8 + 3
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6 * float(np.abs(w).max() + 1))
    # the selection bias selects and is not trained; the head is no
    # transpose of the embedding
    assert float(np.abs(got_g["layer_1"]["moe"]["expert_bias"]).max()) == 0
    assert float(np.abs(got_g["lm_head"]).max()) > 0
    assert params["lm_head"].shape == \
        params["word_embeddings"]["embedding"].shape == (512, 64)


def test_rotation_of_pairs_equals_the_deinterleaved_halves():
    """The pairing ``(2i, 2i + 1)`` turned in place (the reference) and
    the class's form, de-interleave then rotate halves (the program):
    the same numbers in another order, the same order on queries and
    keys, so every product of a query with a key is the same."""
    seq, heads, d, theta = 24, 3, 8, 1e6
    q, k = (jax.random.normal(key, (2, seq, heads, d))
            for key in jax.random.split(jax.random.PRNGKey(0)))
    cos, sin = deepseek_v3.rotary_tables(seq, d, theta)
    got_q, got_k = (deepseek_v3.rotate_pairs(x, cos, sin) for x in (q, k))
    want_q, want_k = (reference.rotary_pairs(x, theta) for x in (q, k))
    order = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    np.testing.assert_allclose(np.asarray(got_q),
                               np.asarray(want_q)[..., order], rtol=1e-5,
                               atol=1e-6)
    scores = lambda a, b: np.einsum("bqhd,bkhd->bhqk", a, b)
    np.testing.assert_allclose(scores(got_q, got_k), scores(want_q, want_k),
                               rtol=1e-4, atol=1e-5)
    # and the pairs in place are the complex form: channels 2i and
    # 2i + 1 the real and imaginary part of a number turned by t *
    # theta^(-2i / d)
    x64 = np.asarray(q, np.float64)
    turned = (x64[..., 0::2] + 1j * x64[..., 1::2]) * np.exp(
        1j * np.arange(seq)[:, None, None]
        * theta ** (-np.arange(0, d, 2) / d))
    np.testing.assert_allclose(np.asarray(want_q)[..., 0::2], turned.real,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(want_q)[..., 1::2], turned.imag,
                               rtol=1e-5, atol=1e-5)


def test_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Two chips share a layer's routed experts (0 to 3 and 4 to 7 of
    the tiny model's 8): the routed parts the two shares give, added,
    with the shared expert, which every chip computes alike, counted
    ONCE, are the uncut reference's whole layer; and each share's module
    is the reference given the same share."""
    cfg = deepseek_v3.deepseek_v3_tiny_config(dtype=jnp.float32)
    uncut = dict(file_config(cfg), first_expert=0)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
    stack = lambda key, a, b: jax.random.normal(key, (8, a, b)) / np.sqrt(a)
    mat = lambda key, a, b: {"kernel": jax.random.normal(key, (a, b))
                             / np.sqrt(a)}
    p = {"router": jax.random.normal(keys[0], (hidden, 8)),
         "expert_bias": jnp.zeros(8),
         "gate": stack(keys[1], hidden, width),
         "up": stack(keys[2], hidden, width),
         "down": stack(keys[3], width, hidden),
         "shared": {"gate": mat(keys[4], hidden, cfg.shared_width),
                    "up": mat(keys[5], hidden, cfg.shared_width),
                    "out": mat(keys[6], cfg.shared_width, hidden)}}
    x = jax.random.normal(keys[7], (2, 64, hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.sparse_ffn(x, p, uncut)
        always = reference.swiglu(
            x, *(p["shared"][n]["kernel"] for n in ("gate", "up", "out")))
        routed, pairs = [], 0
        for first in (0, 4):
            of_share = lambda name: p[name][first:first + 4]
            share = {**p, **{n: of_share(n) for n in ("gate", "up", "down")}}
            module = deepseek_v3.sparse_ffn(
                dataclasses.replace(cfg, first_expert=first), None)
            got = jax.jit(module.apply)({"params": share}, x)
            alone, _ = reference.sparse_ffn(
                x, share, dict(uncut, first_expert=first))
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       rtol=2e-4, atol=2e-5)
            routed.append(got - always)
            y, routing = moe.routed_experts(
                x.reshape(-1, hidden), p["router"], p["expert_bias"],
                of_share("gate"), of_share("up"), of_share("down"),
                first_expert=first, top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor,
                gate_sum_eps=deepseek_v3.GATE_SUM_EPS)
            np.testing.assert_allclose(np.asarray(y.reshape(x.shape)),
                                       np.asarray(routed[-1]), rtol=2e-4,
                                       atol=2e-5)
            pairs += int(moe.held_pairs(routing, first,
                                        4)[0].group_sizes.sum())
    assert pairs == 128 * 2
    np.testing.assert_allclose(np.asarray(sum(routed) + always),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("eps", [moe.GATE_SUM_EPS, 1e-20, 0.5])
def test_the_gates_denominator_takes_the_familys_epsilon(eps):
    """``sigmoid_top_k`` divides the chosen gates by their sum plus the
    epsilon it is given, today's 1e-6 where it is given none, and
    multiplies by the routed scale."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    plain = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, normalize=False)
    got = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, scale=2.448,
                            gate_sum_eps=eps)
    want = 2.448 * plain.gates / (plain.gates.sum(-1, keepdims=True) + eps)
    np.testing.assert_allclose(np.asarray(got.gates), np.asarray(want),
                               rtol=1e-6)
    if eps == moe.GATE_SUM_EPS:
        default = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, scale=2.448)
        assert (np.asarray(default.gates) == np.asarray(got.gates)).all()


@pytest.mark.parametrize("axes", [None, {"dp": 2, "tp": 2}],
                         ids=["direct", "dp2xtp2"])
def test_latent_attention_through_the_kernels_equals_its_einsums(axes):
    """The tiny model with the flash kernels at two widths (forced to
    interpret mode, under ``jit``), for which every head's key is laid
    out with the one rotated key behind it, against the einsum path,
    which reads the one rotated key as it is: the logits and the
    gradients of the latent path's leaves."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = axes and NamedSharding(
        build_mesh(axes, jax.devices()[:4]), P("dp", None, "tp", None))
    cfg_e, m_e, params, ids = tiny(attention_impl="einsum")
    ids = ids[:, :48]
    m_f = deepseek_v3.DeepseekV3LMHeadModel(
        dataclasses.replace(cfg_e, attention_impl="flash"),
        heads_sharding=sharding)

    def value_and_grad(model):
        def loss(p):
            hidden, head = model.apply(
                {"params": p}, ids,
                method=deepseek_v3.DeepseekV3LMHeadModel.hidden_and_embedding)
            return _tied_head_loss(None, hidden, head, ids), hidden
        (_, hidden), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return hidden, grads

    hidden_e, grads_e = value_and_grad(m_e)
    with pltpu.force_tpu_interpret_mode():
        hidden_f, grads_f = value_and_grad(m_f)
    np.testing.assert_allclose(np.asarray(hidden_f), np.asarray(hidden_e),
                               atol=2e-4, rtol=2e-4)
    for name in ("query", "kv_down", "kv_up", "out"):
        want = np.asarray(grads_e["layer_1"]["attention"][name]["kernel"])
        np.testing.assert_allclose(
            np.asarray(grads_f["layer_1"]["attention"][name]["kernel"]),
            want, rtol=1e-3, atol=1e-4 * float(np.abs(want).max()),
            err_msg=name)


@pytest.mark.parametrize("bad,match", [
    (dict(qk_rope_head_dim=32), "rotary part"),
    (dict(qk_rope_head_dim=7), "pairs"),
    (dict(first_expert=6, experts_held=4), "experts held"),
    (dict(experts_held=9), "experts held"),
    (dict(first_k_dense_replace=4), "first_k_dense_replace")],
    ids=["rope-over-qk", "odd-rope", "held-past-the-router",
         "more-held-than-routed", "more-dense-than-layers"])
def test_config_refuses_what_the_model_cannot_build(bad, match):
    with pytest.raises(ValueError, match=match):
        deepseek_v3.deepseek_v3_tiny_config(**bad)


def _tiny_step(axes, **config):
    cfg = deepseek_v3.deepseek_v3_tiny_config(dtype=jnp.float32, **config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = make_deepseek_v3_train_step(cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                             cfg.vocab_size)
    return cfg, mesh, init_fn, step_fn, jax.device_put(ids, batch_sharding)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_on_dp_by_tp_equals_one_device(remat):
    """``make_deepseek_v3_train_step`` under its partition rules on dp2
    x tp2: the loss the step returns is the one-device loss of the same
    parameters; the query, the up-projection and the output projection
    are split by heads over ``tp``, the down-projection and the router
    whole, the dense SwiGLU, the shared expert and every routed expert
    by columns, the embedding and the head by rows."""
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=remat)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    spec = lambda *path: tuple(jax.tree_util.tree_reduce(
        lambda t, k: t[k], path, params).sharding.spec)
    assert spec("layer_1", "attention", "query", "kernel")[1] == "tp"
    assert spec("layer_1", "attention", "kv_up", "kernel")[1] == "tp"
    assert spec("layer_1", "attention", "out", "kernel")[0] == "tp"
    assert "tp" not in spec("layer_1", "attention", "kv_down", "kernel")
    assert spec("layer_0", "mlp", "gate", "kernel")[1] == "tp"
    assert spec("layer_1", "moe", "shared", "up", "kernel")[1] == "tp"
    assert spec("layer_1", "moe", "shared", "out", "kernel")[0] == "tp"
    assert spec("layer_1", "moe", "gate")[2] == "tp"
    assert spec("layer_1", "moe", "down")[1] == "tp"
    assert "tp" not in spec("layer_1", "moe", "router")
    assert spec("word_embeddings", "embedding")[0] == "tp"
    assert spec("lm_head")[0] == "tp"
    host = jax.device_get(params)
    want = deepseek_v3_step_loss(deepseek_v3.DeepseekV3LMHeadModel(cfg),
                                 host, jax.device_get(ids))
    new_params, _, loss = step_fn(params, opt_state, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # AdamW moved every leaf but the selection bias, which no gradient
    # reaches and no decay touches.
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         new_params, host)
    for layer in ("layer_1", "layer_2"):
        assert moved[layer]["moe"].pop("expert_bias") == 0.0
    assert all(v > 0 for v in jax.tree.leaves(moved))


def test_experts_lie_on_ep_where_the_mesh_has_one():
    cfg = deepseek_v3.deepseek_v3_tiny_config()
    ids = jnp.zeros((2, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: deepseek_v3.DeepseekV3LMHeadModel(cfg).init(
            jax.random.PRNGKey(0), ids)["params"])
    mesh = build_mesh({"dp": 2, "ep": 2}, jax.devices()[:4])
    shardings = infer_shardings(shapes, mesh, deepseek_v3_partition_rules())
    experts = shardings["layer_1"]["moe"]
    assert tuple(experts["gate"].spec)[0] == "ep"
    assert tuple(experts["down"].spec)[0] == "ep"
    assert "ep" not in tuple(experts["router"].spec)
    assert "ep" not in tuple(experts["shared"]["gate"]["kernel"].spec)


def test_gauges_show_in_the_metrics_snapshot():
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=True)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    step_fn.lower(*state, ids)
    gauges = hvd.metrics_snapshot()["gauges"]
    # one device's share: 2 of 4 sequences of 64, 2 of 4 heads
    assert gauges["hvd_mla_heads"] == 2
    assert gauges["hvd_mla_head_dims"] == {
        "which=qk": 24.0, "which=v": 16.0, "which=rope": 8.0,
        "which=latent": 32.0}
    assert gauges["hvd_mla_expand_bytes"] == 128 * 2 * (24 + 16) * 4 \
        == deepseek_v3.expand_bytes(128, cfg, 2)
    assert gauges["hvd_moe_experts"] == {"which=total": 8.0,
                                         "which=held": 4.0}
    assert gauges["hvd_moe_top_k"] == 2
    assert gauges["hvd_moe_dispatch_rows"] == 128 * 2
    assert gauges["hvd_moe_dispatch_bytes"] == moe.dispatch_bytes(
        128, cfg.hidden_size, cfg.moe_intermediate_size, 2, 4, 4)
    assert gauges["hvd_moe_shared_width"] == 2 * cfg.moe_intermediate_size
    layers = gauges["hvd_hybrid_layers"]
    assert (layers["kind=dense"], layers["kind=sparse"]) == (1.0, 2.0)


def test_remat_bytes_by_hand_at_the_published_widths():
    """A token's bytes a name at the published widths, six layers, 16
    of 128 experts held (what the device keeps of them is
    ``test_causal_lm_families.py``'s)."""
    cfg = deepseek_v3.DeepseekV3Config(
        vocab_size=16032, num_hidden_layers=6, experts_held=16)
    per_token = lambda name: deepseek_v3.remat_bytes((name,), 1, 1, cfg)
    assert per_token("flash_out") == 6 * 32 * 128 * 2
    assert per_token("flash_lse") == 6 * 32 * 4
    assert per_token(moe.CHOICE_NAME) == 5 * 6 * 4
    assert per_token("gate_up") == 2 * 2 * (6144 + 5 * 1536)
    assert per_token(moe.EXPERT_GATE_UP_NAME) == 5 * 6 * 2 * 768 * 2
    assert per_token(deepseek_v3.EXPANDED_KV_NAME) == 6 * 32 * 320 * 2
    assert per_token(moe.ROWS_NAME) == 5 * 6 * 2048 * 2
    # sequences and positions count alike
    assert deepseek_v3.remat_bytes(deepseek_v3.REMAT_NAMES, 2, 8192, cfg) \
        == 16384 * sum(map(per_token, deepseek_v3.REMAT_NAMES))


def test_expert_choices_of_a_batch():
    from horovod_tpu.models.lfm2 import counts_by_expert
    cfg, model, params, ids = tiny()
    chosen = deepseek_v3.expert_choices(cfg, params, ids)
    assert sorted(chosen) == [1, 2]
    for c in chosen.values():
        assert c.shape == (128, 2) and int(c.min()) >= 0 and int(c.max()) < 8
        assert int(counts_by_expert(c, 8).sum()) == 128 * 2


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_choice_handed_over_is_computed_on(remat):
    """``deepseek_v3_step_loss(..., chosen=)``: handed its own choice
    the step's loss and gradients are its own, bit for bit; handed
    another the sparse layers compute on it, with this router's scores
    as the gates, and the router still gets a gradient."""
    cfg, model, params, ids = tiny(remat=remat)
    own = deepseek_v3.expert_choices(cfg, params, ids)
    value_and_grad = lambda chosen: jax.jit(jax.value_and_grad(
        lambda p: deepseek_v3_step_loss(model, p, ids, chosen)))(params)
    (plain, plain_g), (same, same_g) = value_and_grad(None), \
        value_and_grad(own)
    assert float(plain) == float(same)
    for a, b in zip(jax.tree.leaves(plain_g), jax.tree.leaves(same_g)):
        assert (np.asarray(a) == np.asarray(b)).all()
    other = {i: (c + 1) % cfg.n_routed_experts for i, c in own.items()}
    moved, moved_g = value_and_grad(other)
    assert float(moved) != float(plain)
    assert float(np.abs(moved_g["layer_1"]["moe"]["router"]).max()) > 0
    # what the layers computed on is what they were handed
    _, state = model.apply(
        {"params": params, **deepseek_v3.given_choices(other)}, ids,
        mutable=["intermediates"], method="hidden_and_embedding")
    sown = state["intermediates"]["layer_2"]["moe"]["chosen"][0]
    assert (np.asarray(sown) == np.asarray(other[2])).all()


def test_sigmoid_top_k_takes_a_choice_and_keeps_its_scores():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    own = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, normalize=False)
    again = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, normalize=False,
                              chosen=own.chosen)
    assert (np.asarray(again.chosen) == np.asarray(own.chosen)).all()
    assert (np.asarray(again.gates) == np.asarray(own.gates)).all()
    given = (own.chosen + 3) % 8
    other = moe.sigmoid_top_k(x, router, jnp.zeros(8), 2, normalize=False,
                              chosen=given)
    scores = jax.nn.sigmoid(x @ router)
    np.testing.assert_allclose(
        np.asarray(other.gates),
        np.asarray(jnp.take_along_axis(scores, given, -1)), rtol=1e-5)
