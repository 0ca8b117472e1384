"""The Qwen3-Next family in the program: the step's loss and every
gradient against the plain reference, the query-and-gate split within a
head, the partial rotation, the experts' shares with the gated shared
expert counted once, attention through the kernels against its einsums,
the step on a dp x tp mesh and its gauges."""

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
from benchmarks.reference import qwen3_next as reference
from horovod_tpu.models import qwen3_next
from horovod_tpu.ops import gated_delta
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.sharding import (infer_shardings,
                                           qwen3_next_partition_rules)
from horovod_tpu.training import (_tied_head_loss, make_qwen3_next_train_step,
                                  qwen3_next_step_loss)


def file_config(cfg: qwen3_next.Qwen3NextConfig) -> dict:
    """``cfg`` under the keys of a configuration file, which is what the
    reference reads."""
    keys = ("num_hidden_layers", "full_attention_interval",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "head_dim",
            "num_attention_heads", "num_key_value_heads",
            "partial_rotary_factor", "rope_theta", "rms_norm_eps",
            "num_experts_per_tok", "norm_topk_prob", "first_expert")
    return {key: getattr(cfg, key) for key in keys}


def moved(params, key):
    """``params`` with the leaves that start at a constant (the norms'
    zeros and ones, ``dt_bias``) moved off it, so that a sign or a
    ``1 +`` that is wrong shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, k in zip(leaves, keys)])


def tiny(**kw):
    """One period: three delta-rule layers and a full one; 2 key heads
    serving 4 value heads of 16; 4 query heads over 2 key-value heads of
    32 with 8 rotated; 16 experts, 4 held, top 3; sequence 96 in chunks
    of 32."""
    cfg = qwen3_next.qwen3_next_tiny_config(dtype=jnp.float32, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0,
                             cfg.vocab_size)
    model = qwen3_next.Qwen3NextLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    return cfg, model, moved(params, jax.random.PRNGKey(2)), ids


@pytest.mark.parametrize("first_expert", [0, 8], ids=["first0", "first8"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_and_every_gradient_equal_the_reference(remat,
                                                          first_expert):
    """The step's own loss (the chunked delta rule, the fused input
    projection, sort-and-gather dispatch under the softmax router, the
    gated shared expert, an untied head over chunks of the sequence)
    against the reference, whose delta rule is the recurrence, position
    by position, and which has no dispatch.  In float32 both choose
    alike."""
    cfg, model, params, ids = tiny(remat=remat, first_expert=first_expert)
    assert cfg.layer_types == (qwen3_next.LINEAR,) * 3 + (qwen3_next.FULL,)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: qwen3_next_step_loss(model, p, ids)))(params)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, {"input_ids": ids},
                                     file_config(cfg))))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = jax.tree.leaves(want_g)
    # a sparse feed-forward of 8 leaves and two norms in every layer; a
    # delta-rule mixer of 6, an attention mixer of 6; embedding, head,
    # final norm
    assert len(flat_got) == len(flat_want) == 4 * 10 + 3 * 6 + 6 + 3
    for (path, g), w in zip(flat_got, flat_want):
        assert float(np.abs(w).max()) > 0, jax.tree_util.keystr(path)
        # float32 on both sides: each lies 1e-4 to 4e-4 of a leaf's
        # largest entry from the same gradient in float64 (four layers
        # of norms over heads of 16 amplify the roundings)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3,
            atol=2e-3 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
    assert params["lm_head"].shape == \
        params["word_embeddings"]["embedding"].shape == (512, 64)


def test_query_and_gate_are_split_within_each_head():
    """A hand-built case: the query projection's columns are ``[head 0's
    query | head 0's gate | head 1's query | ...]``.  With a gate of
    -inf on one head alone (columns ``d .. 2d`` of THAT head), only that
    head's output closes."""
    cfg = qwen3_next.qwen3_next_tiny_config(
        dtype=jnp.float32, num_hidden_layers=1, full_attention_interval=1)
    d, heads = cfg.head_dim, cfg.num_attention_heads
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, cfg.hidden_size))
    cos, sin = qwen3_next.rotary_tables(24, cfg.rotary_dim, cfg.rope_theta)
    module = qwen3_next.GatedAttention(cfg)
    params = module.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    assert params["query"]["kernel"].shape == (cfg.hidden_size, heads, 2 * d)
    # the output projection reads head j's channels alone
    only = lambda j: jnp.zeros_like(params["out"]["kernel"]).at[j].set(
        params["out"]["kernel"][j])
    closed = 1
    kernel = params["query"]["kernel"].at[:, closed, d:].set(0.0)
    with_gate = lambda k: module.apply(
        {"params": {**params, "query": {"kernel": k}}}, x, cos, sin)
    # a gate of 0 everywhere halves every head's output ...
    half = with_gate(params["query"]["kernel"].at[:, :, d:].set(0.0))
    want = reference.gated_attention(
        x, {**params, "query": {
            "kernel": params["query"]["kernel"].at[:, :, d:].set(0.0)}},
        file_config(cfg))
    np.testing.assert_allclose(np.asarray(half), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # ... and the gate columns of head 1 move head 1's output alone
    base = with_gate(params["query"]["kernel"])
    other = with_gate(kernel)
    for j in range(heads):
        p = {**params, "out": {"kernel": only(j)}}
        a = module.apply({"params": p}, x, cos, sin)
        b = module.apply({"params": {**p, "query": {"kernel": kernel}}},
                         x, cos, sin)
        same = np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        assert same is (j != closed), j
    assert not np.allclose(np.asarray(base), np.asarray(other), atol=1e-6)


def test_rotary_positions_turn_the_first_quarter_of_a_head():
    """``partial_rotary_factor`` 0.25 of a head of 32: channels 0 to 7
    turn, ``i`` with ``i + 4``, by ``t * theta^(-2i / 8)``; the other 24
    pass; program and reference alike."""
    seq, d, part, theta = 24, 32, 8, 1e7
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 3, d))
    cos, sin = qwen3_next.rotary_tables(seq, part, theta)
    got = qwen3_next.rotate_part(x, cos, sin)
    want = reference.rotary_part(x, part, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert (np.asarray(got[..., part:]) == np.asarray(x[..., part:])).all()
    x64 = np.asarray(x, np.float64)
    turned = (x64[..., :4] + 1j * x64[..., 4:8]) * np.exp(
        1j * np.arange(seq)[:, None, None]
        * theta ** (-np.arange(0, part, 2) / part))
    np.testing.assert_allclose(np.asarray(got[..., :4]), turned.real,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[..., 4:8]), turned.imag,
                               rtol=1e-4, atol=1e-5)


def test_expert_shares_and_one_gated_shared_expert_add_up_to_the_uncut_layer():
    """Four chips share a layer's routed experts (``first_expert`` 0, 4,
    8, 12 of the tiny model's 16): the routed parts the four shares
    give, added, with the gated shared expert, which every chip computes
    alike, counted ONCE, are the uncut reference's whole layer; and each
    share's module is the reference given the same share."""
    cfg = qwen3_next.qwen3_next_tiny_config(dtype=jnp.float32)
    uncut = dict(file_config(cfg), first_expert=0)
    keys = jax.random.split(jax.random.PRNGKey(0), 9)
    hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
    stack = lambda key, a, b: jax.random.normal(key, (16, a, b)) / np.sqrt(a)
    mat = lambda key, a, b: {"kernel": jax.random.normal(key, (a, b))
                             / np.sqrt(a)}
    p = {"router": jax.random.normal(keys[0], (hidden, 16)),
         "gate": stack(keys[1], hidden, width),
         "up": stack(keys[2], hidden, width),
         "down": stack(keys[3], width, hidden),
         "shared": {"gate": mat(keys[4], hidden, width),
                    "up": mat(keys[5], hidden, width),
                    "out": mat(keys[6], width, hidden)},
         "shared_gate": mat(keys[8], hidden, 1)}
    x = jax.random.normal(keys[7], (2, 96, hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.sparse_ffn(x, p, uncut)
        always = reference.shared_expert(x.reshape(-1, hidden), p).reshape(
            x.shape)
        routed, pairs = [], 0
        for first in (0, 4, 8, 12):
            of_share = lambda name: p[name][first:first + 4]
            share = {**p, **{n: of_share(n) for n in ("gate", "up", "down")}}
            module = qwen3_next.sparse_ffn(
                dataclasses.replace(cfg, first_expert=first), None)
            got = jax.jit(module.apply)({"params": share}, x)
            alone, _ = reference.sparse_ffn(
                x, share, dict(uncut, first_expert=first))
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       rtol=2e-4, atol=2e-5)
            routed.append(got - always)
            _, routing = moe.routed_experts(
                x.reshape(-1, hidden), p["router"], None, of_share("gate"),
                of_share("up"), of_share("down"), first_expert=first,
                top_k=cfg.num_experts_per_tok, router=moe.softmax_top_k)
            pairs += int(moe.held_pairs(routing, first,
                                        4)[0].group_sizes.sum())
    assert pairs == 192 * 3
    np.testing.assert_allclose(np.asarray(sum(routed) + always),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("axes", [None, {"dp": 2, "tp": 2}],
                         ids=["direct", "dp2xtp2"])
def test_gated_attention_through_the_kernels_equals_its_einsums(axes):
    """The tiny model with the flash kernels in its full layer (forced
    to interpret mode, under ``jit``), keys and values laid out for
    every query head, against the einsum path: the hidden states and
    the gradients of the full layer's leaves."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = axes and NamedSharding(
        build_mesh(axes, jax.devices()[:4]), P("dp", None, "tp", None))
    cfg_e, m_e, params, ids = tiny(attention_impl="einsum")
    ids = ids[:, :48]
    m_f = qwen3_next.Qwen3NextLMHeadModel(
        dataclasses.replace(cfg_e, attention_impl="flash"),
        heads_sharding=sharding)

    def value_and_grad(model):
        def loss(p):
            hidden, head = model.apply(
                {"params": p}, ids,
                method=qwen3_next.Qwen3NextLMHeadModel.hidden_and_embedding)
            return _tied_head_loss(None, hidden, head, ids), hidden
        (_, hidden), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return hidden, grads

    hidden_e, grads_e = value_and_grad(m_e)
    with pltpu.force_tpu_interpret_mode():
        hidden_f, grads_f = value_and_grad(m_f)
    np.testing.assert_allclose(np.asarray(hidden_f), np.asarray(hidden_e),
                               atol=1e-3, rtol=1e-3)
    for name in ("query", "key", "value", "out"):
        want = np.asarray(grads_e["layer_3"]["attention"][name]["kernel"])
        np.testing.assert_allclose(
            np.asarray(grads_f["layer_3"]["attention"][name]["kernel"]),
            want, rtol=1e-3, atol=1e-4 * float(np.abs(want).max()),
            err_msg=name)


@pytest.mark.parametrize("bad,match", [
    (dict(linear_num_value_heads=5), "linear_num_key_heads must divide"),
    (dict(num_attention_heads=3), "num_key_value_heads must divide"),
    (dict(first_expert=14, experts_held=4), "experts held"),
    (dict(experts_held=17), "experts held"),
    (dict(partial_rotary_factor=0.1), "rotary positions"),
    (dict(partial_rotary_factor=1.5), "rotary positions"),
    (dict(full_attention_interval=0), "full_attention_interval")],
    ids=["value-heads-no-multiple", "query-heads-no-multiple",
         "held-past-the-router", "more-held-than-routed", "odd-rotary-part",
         "rotary-part-over-the-head", "no-interval"])
def test_config_refuses_what_the_model_cannot_build(bad, match):
    with pytest.raises(ValueError, match=match):
        qwen3_next.qwen3_next_tiny_config(**bad)


def test_layer_kinds_follow_the_interval():
    kinds = qwen3_next.Qwen3NextConfig().layer_types
    assert len(kinds) == 48 and kinds.count(qwen3_next.FULL) == 12
    assert [i for i, k in enumerate(kinds) if k == qwen3_next.FULL] == \
        list(range(3, 48, 4))
    cfg = qwen3_next.Qwen3NextConfig()
    assert (cfg.rotary_dim, cfg.linear_conv_dim, cfg.linear_in_proj_dim) == \
        (64, 8192, 12352)


def _tiny_step(axes, **config):
    cfg = qwen3_next.qwen3_next_tiny_config(dtype=jnp.float32, **config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = make_qwen3_next_train_step(cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 96), 0,
                             cfg.vocab_size)
    return cfg, mesh, init_fn, step_fn, jax.device_put(ids, batch_sharding)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_on_dp_by_tp_equals_one_device(remat):
    """``make_qwen3_next_train_step`` under its partition rules on dp2 x
    tp2: the loss the step returns is the one-device loss of the same
    parameters; the query, key, value and the two output projections are
    split by heads over ``tp``, the delta rule's input projection and
    the router whole, the shared expert and every routed expert by
    columns, the embedding and the head by rows."""
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=remat)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    spec = lambda *path: tuple(jax.tree_util.tree_reduce(
        lambda t, k: t[k], path, params).sharding.spec)
    assert spec("layer_3", "attention", "query", "kernel")[1] == "tp"
    assert spec("layer_3", "attention", "key", "kernel")[1] == "tp"
    assert spec("layer_3", "attention", "out", "kernel")[0] == "tp"
    assert "tp" not in spec("layer_0", "linear_attention", "in_proj",
                            "kernel")
    assert spec("layer_0", "linear_attention", "out_proj", "kernel")[0] \
        == "tp"
    assert spec("layer_0", "linear_attention", "A_log") == ("tp",)
    assert spec("layer_0", "linear_attention", "dt_bias") == ("tp",)
    assert spec("layer_1", "moe", "shared", "up", "kernel")[1] == "tp"
    assert spec("layer_1", "moe", "shared", "out", "kernel")[0] == "tp"
    assert spec("layer_1", "moe", "gate")[2] == "tp"
    assert spec("layer_1", "moe", "down")[1] == "tp"
    assert "tp" not in spec("layer_1", "moe", "router")
    assert "tp" not in spec("layer_1", "moe", "shared_gate", "kernel")
    assert spec("word_embeddings", "embedding")[0] == "tp"
    assert spec("lm_head")[0] == "tp"
    host = jax.device_get(params)
    want = qwen3_next_step_loss(qwen3_next.Qwen3NextLMHeadModel(cfg), host,
                                jax.device_get(ids))
    new_params, _, loss = step_fn(params, opt_state, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # AdamW moved every leaf.
    moved_by = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()), new_params,
        host)
    assert all(v > 0 for v in jax.tree.leaves(moved_by))


def test_decay_leaves_the_vectors_alone():
    """AdamW's decay takes the leaves of two dimensions or more (the
    step's mask): the vectors it leaves alone are the norms, ``A_log``
    and ``dt_bias`` and nothing else; the taps, the shared expert's
    gate column and the experts' stacks are matrices."""
    cfg = qwen3_next.qwen3_next_tiny_config()
    ids = jnp.zeros((2, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: qwen3_next.Qwen3NextLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), ids)["params"])
    vectors = {jax.tree_util.keystr(path)
               for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
               if leaf.ndim < 2}
    mixer = "['layer_0']['linear_attention']"
    assert {mixer + "['A_log']", mixer + "['dt_bias']",
            mixer + "['norm_scale']", "['final_norm']['scale']",
            "['layer_3']['attention']['query_norm']['scale']"} <= vectors
    assert all("norm" in v or "A_log" in v or "dt_bias" in v
               for v in vectors)
    # the taps, the shared expert's gate column and the experts' stacks
    # are matrices
    assert shapes["layer_0"]["linear_attention"]["conv_kernel"].ndim == 2
    assert shapes["layer_0"]["moe"]["shared_gate"]["kernel"].shape == (64, 1)


def test_experts_lie_on_ep_where_the_mesh_has_one():
    cfg = qwen3_next.qwen3_next_tiny_config()
    ids = jnp.zeros((2, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: qwen3_next.Qwen3NextLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), ids)["params"])
    mesh = build_mesh({"dp": 2, "ep": 2}, jax.devices()[:4])
    shardings = infer_shardings(shapes, mesh, qwen3_next_partition_rules())
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
    # one device's share: 2 of 4 sequences of 96, 2 of 4 value heads
    assert gauges["hvd_gdn_heads"] == {"which=value": 2.0, "which=key": 1.0}
    assert gauges["hvd_gdn_head_dims"] == {"which=key": 16.0,
                                           "which=value": 16.0}
    assert gauges["hvd_gdn_chunks"] == 3
    assert gauges["hvd_gdn_scan_bytes"] == gated_delta.scan_bytes(
        2, 96, 2, 16, 16, 32, 4)
    assert gauges["hvd_attention_kv_repeat"] == 2
    assert gauges["hvd_attention_head_dim"] == 32
    assert gauges["hvd_moe_experts"] == {"which=total": 16.0,
                                         "which=held": 4.0}
    assert gauges["hvd_moe_top_k"] == 3
    assert gauges["hvd_moe_dispatch_rows"] == 192 * 3
    assert gauges["hvd_moe_dispatch_bytes"] == moe.dispatch_bytes(
        192, cfg.hidden_size, cfg.moe_intermediate_size, 3, 4, 4)
    assert gauges["hvd_moe_router"]["kind=softmax"] == 1
    assert gauges["hvd_moe_shared_width"] == 32
    layers = gauges["hvd_hybrid_layers"]
    assert (layers["kind=linear_attention"],
            layers["kind=full_attention"]) == (3.0, 1.0)


def test_the_step_differentiates_through_the_written_out_walk():
    """``hvd_gdn_walk_traces{pass}``: a traced step traces the walk's
    backward rule once a delta-rule layer, so its gradient is the
    written-out reverse scan and not autodiff's of the forward one, and
    its forward rule at least as often (``remat`` traces a layer's
    forward pass more than once)."""
    walks = gated_delta._WALK_TRACES
    was = {p: walks.value(**{"pass": p}) for p in ("forward", "backward")}
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 1}, remat=True)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    step_fn.lower(*state, ids)
    traced = hvd.metrics_snapshot()["gauges"]["hvd_gdn_walk_traces"]
    layers = cfg.layer_types.count(qwen3_next.LINEAR)
    assert traced["pass=backward"] == was["backward"] + layers == \
        walks.value(**{"pass": "backward"})
    assert traced["pass=forward"] >= was["forward"] + layers


def test_remat_bytes_by_hand_at_the_published_widths():
    """One sequence of 8192 at the published widths, one period, 32 of
    512 experts held (what the device keeps of them is
    ``test_causal_lm_families.py``'s)."""
    cfg = qwen3_next.Qwen3NextConfig(vocab_size=18992, num_hidden_layers=4,
                                     experts_held=32)
    per = lambda name: qwen3_next.remat_bytes((name,), 1, 8192, cfg)
    assert per("flash_out") == 8192 * 16 * 256 * 2
    assert per("flash_lse") == 8192 * 16 * 4
    assert per(moe.CHOICE_NAME) == 8192 * 4 * 10 * 4
    assert per("gate_up") == 8192 * 4 * 2 * 512 * 2
    assert per(gated_delta.WY_NAME) == 8192 * 3 * 32 * (128 * 2 + 128 * 4)
    assert per("in_proj") == 8192 * 3 * 12352 * 2
    assert per(moe.EXPERT_GATE_UP_NAME) == 8192 * 4 * 10 * 2 * 512 * 2
    assert per(moe.ROWS_NAME) == 8192 * 4 * 10 * 2048 * 2
    # v_new of every position and a state a chunk, float32
    assert per(gated_delta.STATES_NAME) == 3 * 32 * 4 * (
        8192 * 128 + 128 * 128 * 128)


def test_expert_choices_of_a_batch():
    from horovod_tpu.models.lfm2 import counts_by_expert
    cfg, model, params, ids = tiny()
    chosen = qwen3_next.expert_choices(cfg, params, ids)
    assert sorted(chosen) == [0, 1, 2, 3]
    for c in chosen.values():
        assert c.shape == (192, 3) and int(c.min()) >= 0 \
            and int(c.max()) < 16
        assert int(counts_by_expert(c, 16).sum()) == 192 * 3


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_choice_handed_over_is_computed_on(remat):
    """``qwen3_next_step_loss(..., chosen=)``: handed its own choice the
    step's loss and gradients are its own, bit for bit; handed another
    the sparse layers compute on it, with this router's scores as the
    gates, and the router still gets a gradient."""
    cfg, model, params, ids = tiny(remat=remat)
    own = qwen3_next.expert_choices(cfg, params, ids)
    value_and_grad = lambda chosen: jax.jit(jax.value_and_grad(
        lambda p: qwen3_next_step_loss(model, p, ids, chosen)))(params)
    (plain, plain_g), (same, same_g) = value_and_grad(None), \
        value_and_grad(own)
    assert float(plain) == float(same)
    for a, b in zip(jax.tree.leaves(plain_g), jax.tree.leaves(same_g)):
        assert (np.asarray(a) == np.asarray(b)).all()
    other = {i: (c + 1) % cfg.num_experts for i, c in own.items()}
    shifted, shifted_g = value_and_grad(other)
    assert float(shifted) != float(plain)
    assert float(np.abs(shifted_g["layer_1"]["moe"]["router"]).max()) > 0
    _, state = model.apply(
        {"params": params, **qwen3_next.given_choices(other)}, ids,
        mutable=["intermediates"], method="hidden_and_embedding")
    sown = state["intermediates"]["layer_2"]["moe"]["chosen"][0]
    assert (np.asarray(sown) == np.asarray(other[2])).all()
