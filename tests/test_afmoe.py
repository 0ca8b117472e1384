"""The AFMoE family in the program: the step's loss and every gradient
against the plain reference, the bias the step moves against the
reference's rule, the experts' shares with the shared expert counted
once, what tells positions apart in a full and in a window layer, both
kinds of attention through the kernels against their einsums, the step
on a dp x tp mesh and its gauges."""

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
from benchmarks.reference import afmoe as reference
from horovod_tpu.models import afmoe
from horovod_tpu.models.layers import rotary_tables
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.attention import ring_attention
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.sharding import (afmoe_partition_rules,
                                           infer_shardings)
from horovod_tpu.training import (_tied_head_loss, afmoe_step_loss,
                                  make_afmoe_train_step)


def file_config(cfg: afmoe.AfmoeConfig) -> dict:
    """``cfg`` under the keys of a configuration file, which is what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        num_dense_layers=cfg.num_dense_layers,
        layer_types=list(cfg.layer_types), hidden_size=cfg.hidden_size,
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, mup_enabled=cfg.mup_enabled,
        num_experts_per_tok=cfg.num_experts_per_tok,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        load_balance_coeff=cfg.load_balance_coeff,
        first_expert=cfg.first_expert)


def tiny(**kw):
    """A dense window layer, then three window layers and a full one
    with routed experts; 4 query heads over 2 key-value heads of 16, a
    window of 8; 8 experts, 4 held, top 2, scale 2.826."""
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                             cfg.vocab_size)
    model = afmoe.AfmoeLMHeadModel(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1), ids)["params"], ids


@pytest.mark.parametrize("first_expert", [0, 4], ids=["first0", "first4"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_and_every_gradient_equal_the_reference(remat,
                                                          first_expert):
    """The step's own loss (a mask of the band on the window layers and
    the plain triangle on the full one, rotation on the first alone,
    normed and gated heads, four norms a layer, the scaled embedding,
    sort-and-gather dispatch, the shared expert, an untied head over
    chunks of the sequence) against the reference, which makes its mask
    from the positions block by block.  In float32 both choose alike."""
    cfg, model, params, ids = tiny(remat=remat, first_expert=first_expert)
    assert cfg.route_scale == 2.826 and cfg.mup_enabled
    assert cfg.layer_types.count(afmoe.FULL) == 1 and cfg.sliding_window < 64
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: afmoe_step_loss(model, p, ids)))(params)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, {"input_ids": ids},
                                     file_config(cfg))))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got, flat_want = jax.tree.leaves(got_g), jax.tree.leaves(want_g)
    # five layers of 6 attention leaves and 4 norms; a dense SwiGLU of
    # 3; four sparse layers of 5 + 3; embedding, head, final norm
    assert len(flat_got) == len(flat_want) == 5 * 10 + 3 + 4 * 8 + 3
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=3e-4,
                                   atol=3e-6 * float(np.abs(w).max() + 1))
    # no gradient reaches the selection bias; the gate and both norms
    # of the heads are trained
    assert float(np.abs(got_g["layer_1"]["moe"]["expert_bias"]).max()) == 0
    attention = got_g["layer_3"]["attention"]
    assert sorted(attention) == ["gate", "key_norm", "key_value", "out",
                                 "query", "query_norm"]
    assert all(float(np.abs(leaf).max()) > 0
               for leaf in jax.tree.leaves(attention))
    assert params["layer_1"]["attention"]["key_value"]["kernel"].shape == \
        (64, 2, 32)


@pytest.mark.parametrize("broken,leaf", [
    ("window-off-by-one", "layer_1/attention/gate/kernel"),
    ("full-layer-rotated", "layer_3/attention/query/kernel"),
    ("gate-left-out", "layer_1/attention/gate/kernel")])
def test_the_comparison_fails_a_program_that_departs(broken, leaf):
    """What decides ``correct`` (``benchmarks/reference/common.py``
    ``compare``, its tolerances as they are) tells the program from one
    whose window holds one key more, one that rotates its full layer
    too, and one whose gate does not gate (its kernel zeroed: every
    channel times a half)."""
    from benchmarks.reference import common
    cfg, model, params, ids = tiny()
    want = file_config(cfg)
    if broken == "window-off-by-one":
        other = dataclasses.replace(cfg, sliding_window=cfg.sliding_window + 1)
    elif broken == "full-layer-rotated":
        # No layer's window binds, so that rotation alone tells the two
        # kinds apart; the program makes its full layer a window layer.
        want = dict(want, sliding_window=64)
        cfg = dataclasses.replace(cfg, sliding_window=64)
        model = afmoe.AfmoeLMHeadModel(cfg)
        other = dataclasses.replace(
            cfg, layer_types=(afmoe.SLIDING,) * cfg.num_hidden_layers)
    else:
        other = cfg
    departed = afmoe.AfmoeLMHeadModel(other)

    def zero_gates(p):
        if broken != "gate-left-out":
            return p
        return {name: (dict(layer, attention=dict(
            layer["attention"], gate=jax.tree.map(
                jnp.zeros_like, layer["attention"]["gate"])))
            if name.startswith("layer_") else layer)
            for name, layer in p.items()}
    chosen = afmoe.expert_choices(cfg, params, ids)
    ref = lambda p, b: reference.loss(p, b, want, chosen)
    honest = lambda p, b: afmoe_step_loss(model, p, b["input_ids"], chosen)
    wrong = lambda p, b: afmoe_step_loss(departed, zero_gates(p),
                                         b["input_ids"], chosen)
    batch = {"input_ids": ids}
    ok, report = common.compare(honest, ref, params, batch, [leaf])
    assert ok, report
    ok, report = common.compare(wrong, ref, params, batch, [leaf])
    assert not ok, report


@pytest.mark.parametrize("steps", [1, 3])
def test_the_step_moves_the_bias_as_the_reference_does(steps):
    """``make_afmoe_train_step``'s step counts its own choices and moves
    every sparse layer's selection bias after the optimizer's update;
    the reference's rule on the choices the same parameters make gives
    the same bias after one step, to the bit, and after three (the
    second step chooses under the first's bias) to a rounding of the
    last bit: a compiled step may round ``bias + step * x`` once where
    the reference, run eagerly, rounds twice.  Nothing else writes the
    leaf."""
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32)
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    init_fn, step_fn, batch_sharding = make_afmoe_train_step(cfg, mesh)
    ids = jax.device_put(jax.random.randint(
        jax.random.PRNGKey(0), (2, 64), 0, cfg.vocab_size), batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    sparse = [1, 2, 3, 4]
    bias_of = lambda p: {i: np.asarray(p["layer_%d" % i]["moe"]["expert_bias"])
                         for i in sparse}
    want = bias_of(params)
    assert all((b == 0).all() for b in want.values())
    for n in range(steps):
        chosen = afmoe.expert_choices(cfg, params, ids)
        assert sorted(chosen) == sparse
        want = {i: np.asarray(reference.bias_after_update(
            jnp.asarray(want[i]), chosen[i], file_config(cfg)))
            for i in sparse}
        params, opt_state, _ = step_fn(params, opt_state, ids)
        got = bias_of(params)
        for i in sparse:
            assert np.abs(got[i] - want[i]).max() <= 1e-9, (i, got[i],
                                                            want[i])
        if n == 0:
            assert all((got[i] == want[i]).all() for i in sparse)
    for b in want.values():
        assert abs(float(b.sum())) < 1e-8 and float(np.abs(b).max()) > 0
        assert float(np.abs(b).max()) <= steps * 2 * cfg.load_balance_coeff


def test_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Eight chips share a layer's routed experts (one of the tiny
    model's 8 each): the routed parts the eight shares give, added, with
    the shared expert, which every chip computes alike, counted ONCE,
    are the uncut reference's whole layer; and each share's module is
    the reference given the same share."""
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32, experts_held=1)
    uncut = dict(file_config(cfg), first_expert=0)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
    stack = lambda key, a, b: jax.random.normal(key, (8, a, b)) / np.sqrt(a)
    mat = lambda key, a, b: {"kernel": jax.random.normal(key, (a, b))
                             / np.sqrt(a)}
    p = {"router": jax.random.normal(keys[0], (hidden, 8)),
         "expert_bias": 0.01 * jax.random.normal(keys[7], (8,)),
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
        routed = []
        for first in range(8):
            share = {**p, **{n: p[n][first:first + 1]
                             for n in ("gate", "up", "down")}}
            module = afmoe.sparse_ffn(
                dataclasses.replace(cfg, first_expert=first), None)
            got = jax.jit(module.apply)({"params": share}, x)
            alone, _ = reference.sparse_ffn(
                x, share, dict(uncut, first_expert=first))
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       rtol=2e-4, atol=2e-5)
            routed.append(got - always)
    np.testing.assert_allclose(np.asarray(sum(routed) + always),
                               np.asarray(want), rtol=2e-4, atol=3e-5)


@pytest.mark.parametrize("kind,moves", [(afmoe.FULL, False),
                                        (afmoe.SLIDING, True)])
def test_only_a_window_layer_knows_where_a_token_stands(kind, moves):
    """A full layer has no positions: its output at the last token is
    the same whatever order the earlier tokens stand in (every one of
    them shifted by one place, the first to the end), and the same
    under rotary tables of other positions; a window layer's is not:
    its rotation and its window both read the places."""
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32)
    layer = afmoe.GatedAttention(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, cfg.hidden_size))
    tables = lambda first: tuple(
        t[first:] for t in rotary_tables(32 + first, cfg.head_dim,
                                         cfg.rope_theta))
    params = layer.init(jax.random.PRNGKey(1), x, *tables(0))
    out = jax.jit(layer.apply)(params, x, *tables(0))
    shifted = jnp.concatenate([x[:, 1:-1], x[:, :1], x[:, -1:]], axis=1)
    moved = jax.jit(layer.apply)(params, shifted, *tables(0))
    same = np.allclose(np.asarray(out[0, -1]), np.asarray(moved[0, -1]),
                       rtol=1e-4, atol=1e-5)
    assert same is not moves
    if not moves:
        # and no table reaches it at all
        again = jax.jit(layer.apply)(params, x, *tables(5))
        assert (np.asarray(again) == np.asarray(out)).all()


@pytest.mark.parametrize("axes", [None, {"dp": 2, "tp": 2}],
                         ids=["direct", "dp2xtp2"])
def test_both_kinds_of_attention_through_the_kernels_equal_their_einsums(
        axes):
    """The tiny model with the flash kernels (forced to interpret mode,
    under ``jit``): the window layers through the band's walk (a window
    of 8 in tiles of the whole 48-token sequence), the full one through
    the triangle's, against the einsum path under ``visible_keys``: the
    hidden states and the gradients of attention's leaves in a window
    layer and in the full one."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = axes and NamedSharding(
        build_mesh(axes, jax.devices()[:4]), P("dp", None, "tp", None))
    cfg_e, m_e, params, ids = tiny(attention_impl="einsum")
    ids = ids[:, :48]
    m_f = afmoe.AfmoeLMHeadModel(
        dataclasses.replace(cfg_e, attention_impl="flash"),
        heads_sharding=sharding)

    def value_and_grad(model):
        def loss(p):
            hidden, head = model.apply(
                {"params": p}, ids,
                method=afmoe.AfmoeLMHeadModel.hidden_and_embedding)
            return _tied_head_loss(None, hidden, head, ids), hidden
        (_, hidden), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return hidden, grads

    hidden_e, grads_e = value_and_grad(m_e)
    with pltpu.force_tpu_interpret_mode():
        hidden_f, grads_f = value_and_grad(m_f)
    np.testing.assert_allclose(np.asarray(hidden_f), np.asarray(hidden_e),
                               atol=2e-4, rtol=2e-4)
    for layer in ("layer_1", "layer_3"):
        for name in ("query", "key_value", "gate", "out"):
            want = np.asarray(grads_e[layer]["attention"][name]["kernel"])
            np.testing.assert_allclose(
                np.asarray(grads_f[layer]["attention"][name]["kernel"]),
                want, rtol=1e-3, atol=1e-4 * float(np.abs(want).max()),
                err_msg=layer + "/" + name)


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=("conv",) * 5), "layer_types"),
    (dict(layer_types=(afmoe.FULL,) * 4), "layer_types"),
    (dict(num_key_value_heads=3), "num_key_value_heads"),
    (dict(first_expert=6, experts_held=4), "experts held"),
    (dict(num_dense_layers=6), "num_dense_layers"),
    (dict(head_dim=15), "halves"),
    (dict(sliding_window=0), "window")],
    ids=["unknown-kind", "a-kind-short", "ragged-groups",
         "held-past-the-router", "more-dense-than-layers", "odd-head",
         "no-window"])
def test_config_refuses_what_the_model_cannot_build(bad, match):
    with pytest.raises(ValueError, match=match):
        afmoe.afmoe_tiny_config(**bad)


def test_the_published_stack_by_default():
    cfg = afmoe.AfmoeConfig()
    assert cfg.layer_types.count(afmoe.FULL) == 8
    assert [i for i, kind in enumerate(cfg.layer_types)
            if kind == afmoe.FULL] == list(range(3, 32, 4))
    assert cfg.ffn_types[:3] == (afmoe.DENSE, afmoe.DENSE, afmoe.SPARSE)
    assert cfg.shared_width == 1024 and cfg.load_balance_coeff == 1e-3


def test_the_ring_refuses_a_window():
    x = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(NotImplementedError, match="window"):
        ring_attention(x, x, x, causal=True, window=4)


def _tiny_step(axes, **config):
    cfg = afmoe.afmoe_tiny_config(dtype=jnp.float32, **config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = make_afmoe_train_step(cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                             cfg.vocab_size)
    return cfg, mesh, init_fn, step_fn, jax.device_put(ids, batch_sharding)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_on_dp_by_tp_equals_one_device(remat):
    """``make_afmoe_train_step`` under its partition rules on dp2 x tp2:
    the loss the step returns is the one-device loss of the same
    parameters; the query, the gate, the fused key and value and the
    output projection are split by heads over ``tp``, the router and
    its bias whole, the dense SwiGLU, the shared expert and every
    routed expert by columns, the embedding and the head by rows."""
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=remat)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    spec = lambda *path: tuple(jax.tree_util.tree_reduce(
        lambda t, k: t[k], path, params).sharding.spec)
    for name in ("query", "gate", "key_value"):
        assert spec("layer_1", "attention", name, "kernel")[1] == "tp"
    assert spec("layer_1", "attention", "out", "kernel")[0] == "tp"
    assert spec("layer_0", "mlp", "gate", "kernel")[1] == "tp"
    assert spec("layer_1", "moe", "shared", "out", "kernel")[0] == "tp"
    assert spec("layer_1", "moe", "gate")[2] == "tp"
    assert "tp" not in spec("layer_1", "moe", "router")
    assert "tp" not in spec("layer_1", "moe", "expert_bias")
    assert spec("word_embeddings", "embedding")[0] == "tp"
    assert spec("lm_head")[0] == "tp"
    host = jax.device_get(params)
    want = afmoe_step_loss(afmoe.AfmoeLMHeadModel(cfg), host,
                           jax.device_get(ids))
    new_params, _, loss = step_fn(params, opt_state, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # AdamW moved every leaf it has a gradient for, the rule the bias
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         new_params, host)
    assert all(v > 0 for v in jax.tree.leaves(moved))


def test_experts_lie_on_ep_where_the_mesh_has_one():
    cfg = afmoe.afmoe_tiny_config()
    ids = jnp.zeros((2, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: afmoe.AfmoeLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), ids)["params"])
    mesh = build_mesh({"dp": 2, "ep": 2}, jax.devices()[:4])
    shardings = infer_shardings(shapes, mesh, afmoe_partition_rules())
    experts = shardings["layer_1"]["moe"]
    assert tuple(experts["gate"].spec)[0] == "ep"
    assert tuple(experts["down"].spec)[0] == "ep"
    assert "ep" not in tuple(experts["router"].spec)
    assert "ep" not in tuple(experts["expert_bias"].spec)
    assert "ep" not in tuple(experts["shared"]["gate"]["kernel"].spec)


def test_gauges_scopes_and_the_counter():
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=True)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    before = hvd.metrics_snapshot()["counters"].get(
        "hvd_moe_bias_updates_total", 0)
    text = step_fn.lower(*state, ids).as_text(debug_info=True)
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_attention_window"] == 8
    assert gauges["hvd_attention_kv_repeat"] == 2
    assert gauges["hvd_attention_head_dim"] == 16
    from horovod_tpu.ops.pallas_attention import band_tiles
    tiles = band_tiles(64, 8)
    assert gauges["hvd_flash_window_tiles"] == {
        "which=%s" % k: float(tiles[k])
        for k in ("walked", "masked", "skipped")}
    assert gauges["hvd_flash_window_fill"] == tiles["fill"]
    assert gauges["hvd_moe_bias_step"] == 1e-3
    assert gauges["hvd_moe_experts"] == {"which=total": 8.0,
                                         "which=held": 4.0}
    assert gauges["hvd_moe_top_k"] == 2
    assert gauges["hvd_moe_shared_width"] == cfg.moe_intermediate_size
    assert gauges["hvd_moe_router"]["kind=sigmoid"] == 1
    layers = gauges["hvd_hybrid_layers"]
    assert (layers["kind=sliding_attention"], layers["kind=full_attention"],
            layers["kind=dense"], layers["kind=sparse"]) == (4, 1, 1, 4)
    for scope in ("attention/query", "attention/key_value", "attention/gate",
                  "attention/qk_norm", "attention/rotary", "attention/out",
                  "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
                  "bias_update"):
        assert scope in text, scope
    # the full layer rotates nothing
    assert "layer_3/attention/rotary" not in text
    assert "layer_2/attention/rotary" in text
    # a lowering is no step: the counter counts calls
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    step_fn(params, opt_state, ids)
    assert hvd.metrics_snapshot()["counters"][
        "hvd_moe_bias_updates_total"] == before + 1


def test_remat_bytes_by_hand_at_the_published_widths():
    """A token's bytes a name at the published widths, five layers (one
    dense), 16 of 128 experts held."""
    cfg = afmoe.AfmoeConfig(vocab_size=25024, num_hidden_layers=5,
                            num_dense_layers=1, experts_held=16,
                            layer_types=(afmoe.SLIDING,) * 5)
    per_token = lambda name: afmoe.remat_bytes((name,), 1, 1, cfg)
    assert per_token("flash_out") == 5 * 32 * 128 * 2
    assert per_token("flash_lse") == 5 * 32 * 4
    assert per_token(moe.CHOICE_NAME) == 4 * 8 * 4
    assert per_token("gate_up") == 2 * 2 * (6144 + 4 * 1024)
    assert per_token(afmoe.ATTENTION_IN_NAME) == 5 * 2 * (
        32 * 128 + 32 * 128 + 4 * 256)
    assert per_token(moe.EXPERT_GATE_UP_NAME) == 4 * 8 * 2 * 1024 * 2
    assert per_token(moe.ROWS_NAME) == 4 * 8 * 2048 * 2
    assert afmoe.remat_bytes(afmoe.REMAT_NAMES, 1, 16384, cfg) \
        == 16384 * sum(map(per_token, afmoe.REMAT_NAMES))
    assert afmoe.REMAT_CANDIDATES[-1] == afmoe.KEPT_NAMES


def test_expert_choices_of_a_batch():
    from horovod_tpu.models.layers import counts_by_expert
    cfg, model, params, ids = tiny()
    chosen = afmoe.expert_choices(cfg, params, ids)
    assert sorted(chosen) == [1, 2, 3, 4]
    for c in chosen.values():
        assert c.shape == (128, 2) and int(c.min()) >= 0 and int(c.max()) < 8
        assert int(counts_by_expert(c, 8).sum()) == 128 * 2
