"""The Keye-VL family in the program: the step's loss, both its parts
and every gradient against the plain reference (text's positions and
three unequal streams), which part teaches which leaf, a given
selection, the experts' shares, the layer through the kernels against
its einsums, rotary tables by sections, and the step's gauges and
scopes."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import horovod_tpu as hvd
from benchmarks.reference import keye_vl as reference
from horovod_tpu.models import keye_vl
from horovod_tpu.models.layers import given_choices, rotary_tables
from horovod_tpu.ops import dsa
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.training import (keye_vl_loss_parts, keye_vl_step_loss,
                                  make_keye_vl_train_step)


def file_config(cfg: keye_vl.KeyeVLConfig) -> dict:
    """``cfg`` under the keys of a configuration file, which is what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        rope_scaling={"mrope_section": list(cfg.mrope_section)},
        sa_config={"indexer_head_dim": cfg.indexer_head_dim,
                   "indexer_num_heads": cfg.indexer_num_heads,
                   "topk": cfg.topk},
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, first_expert=cfg.first_expert)


def tiny(**kw):
    """Two layers; 4 query heads over 2 key-value heads of 16 in sections
    of 2, 3 and 3 pairs; an indexer of 3 heads of 8 that keeps 24 of up
    to 64 keys; 8 experts, 4 held, top 2."""
    cfg = keye_vl.keye_vl_tiny_config(dtype=jnp.float32, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                             cfg.vocab_size)
    model = keye_vl.KeyeVLLMHeadModel(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1), ids)["params"], ids


# Three streams that differ: a token's place, a third of it, and a count
# that starts over every seven.
STREAMS = jnp.stack([jnp.arange(64), jnp.arange(64) // 3,
                     5 + jnp.arange(64) % 7]).astype(jnp.float32)


@pytest.mark.parametrize("positions", [None, STREAMS],
                         ids=["text", "three-streams"])
@pytest.mark.parametrize("remat,first_expert", [(False, 0), (True, 4)],
                         ids=["plain-first0", "remat-first4"])
def test_step_loss_parts_and_every_gradient_equal_the_reference(
        remat, first_expert, positions):
    """The step's own loss (the indexer's selection as a packed mask,
    attention under it, the alignment loss with its written-out
    backward, rotary sections, softmax-routed experts, an untied head
    over chunks) against the reference, whose selection is a ``top_k``
    and whose gradient is ``jax.grad`` through its two
    ``stop_gradient``s.  In float32 both select and choose alike."""
    cfg, model, params, ids = tiny(remat=remat, first_expert=first_expert)
    assert cfg.topk < 64 and cfg.mrope_section == (2, 3, 3)
    batch = {"input_ids": ids, "positions": positions}
    def program(p):
        lm, aligned, _ = keye_vl_loss_parts(model, p, ids,
                                            positions=positions)
        return lm + aligned, (lm, aligned)

    def plain(p):
        lm, aligned, _ = reference.loss_parts(p, batch, file_config(cfg))
        return lm + aligned, (lm, aligned)
    with jax.default_matmul_precision("highest"):
        (got, parts), got_g = jax.jit(jax.value_and_grad(
            program, has_aux=True))(params)
        (want, want_parts), want_g = jax.jit(jax.value_and_grad(
            plain, has_aux=True))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for a, b in zip(parts, want_parts):
        assert float(a) == pytest.approx(float(b), rel=5e-6)
    assert 0.05 < float(parts[1]) < float(parts[0])
    got_g, want_g = flatten_dict(got_g, sep="/"), flatten_dict(want_g, sep="/")
    assert sorted(got_g) == sorted(want_g)
    for name, want_leaf in want_g.items():
        np.testing.assert_allclose(
            np.asarray(got_g[name]), np.asarray(want_leaf), rtol=2e-4,
            atol=2e-5 * float(np.abs(want_leaf).max()), err_msg=name)
    if positions is not None:   # the streams are read: text's loss is another
        with jax.default_matmul_precision("highest"):
            text = keye_vl_step_loss(model, params, ids)
        assert abs(float(text) - float(got)) > 1e-4


def test_each_part_of_the_loss_teaches_its_own_leaves():
    """The indexer's three matrices and its norm get NO gradient from
    the next-token loss, and nothing else gets any from the alignment
    loss."""
    cfg, model, params, ids = tiny()
    part = lambda n: jax.jit(jax.grad(
        lambda p: keye_vl_loss_parts(model, p, ids)[n]))(params)
    of_lm, of_alignment = (flatten_dict(part(n), sep="/") for n in (0, 1))
    indexer = [name for name in of_lm if "/indexer_" in name]
    assert len(indexer) == cfg.num_hidden_layers * 5
    for name in of_lm:
        lm = float(np.abs(of_lm[name]).max())
        aligned = float(np.abs(of_alignment[name]).max())
        if name in indexer:
            assert lm == 0.0 and aligned > 0.0, name
        else:
            assert aligned == 0.0 and lm > 0.0, name


def test_a_layer_takes_a_given_selection_as_it_takes_a_given_choice():
    """Given its indexer's own selection a layer computes what it
    computes alone; given another (every causal key) it computes on
    that, and still says what its indexer would have kept."""
    cfg, model, params, ids = tiny()
    own = keye_vl.selections(cfg, params, ids)
    assert sorted(own) == [0, 1] and own[0].shape == (2, 2, 64)
    kept = dsa.unpack_mask(own[1])
    assert np.asarray(kept.sum(-1))[0].tolist() \
        == [min(t + 1, cfg.topk) for t in range(64)]
    loss = jax.jit(lambda chosen, selected: keye_vl_step_loss(
        model, params, ids, chosen, selected))
    alone = float(loss(None, None))
    assert float(loss(None, own)) == pytest.approx(alone, rel=1e-6)
    every = {i: dsa.pack_mask(jnp.tril(jnp.ones((2, 64, 64), bool)))
             for i in own}
    assert abs(float(loss(None, every)) - alone) > 1e-3
    chosen = keye_vl.expert_choices(cfg, params, ids)
    assert float(loss(chosen, own)) == pytest.approx(alone, rel=1e-6)
    merged = keye_vl.given(chosen, own)["given"]["layer_1"]
    assert sorted(merged) == ["attention", "moe"]
    assert keye_vl.given() == {} and given_choices({}) == {"given": {}}
    sown = keye_vl_loss_parts(model, params, ids, None, every)[2]
    np.testing.assert_array_equal(keye_vl.sown_of(sown, "selected")[0],
                                  own[0])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips share a layer's routed experts (one of the tiny
    model's 8 each): the eight shares, added, are the uncut reference's
    whole layer (there is no shared expert to count once), and each
    share's module is the reference given the same share."""
    cfg = keye_vl.keye_vl_tiny_config(dtype=jnp.float32, experts_held=1)
    uncut = dict(file_config(cfg), first_expert=0)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
    stack = lambda key, a, b: jax.random.normal(key, (8, a, b)) / np.sqrt(a)
    p = {"router": jax.random.normal(keys[0], (hidden, 8)),
         "gate": stack(keys[1], hidden, width),
         "up": stack(keys[2], hidden, width),
         "down": stack(keys[3], width, hidden)}
    x = jax.random.normal(keys[4], (2, 64, hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.sparse_ffn(x, p, uncut)
        shares = []
        for first in range(8):
            share = {**p, **{n: p[n][first:first + 1]
                             for n in ("gate", "up", "down")}}
            module = keye_vl.sparse_ffn(
                dataclasses.replace(cfg, first_expert=first), None)
            got = jax.jit(module.apply)({"params": share}, x)
            alone, _ = reference.sparse_ffn(
                x, share, dict(uncut, first_expert=first))
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       rtol=2e-4, atol=2e-5)
            shares.append(got)
    np.testing.assert_allclose(np.asarray(sum(shares)), np.asarray(want),
                               rtol=2e-4, atol=3e-5)


def test_the_layer_through_the_kernels_equals_its_einsums():
    """The tiny model with the three kinds of kernels (forced to
    interpret mode, under ``jit``): the selection, attention under its
    bits and the alignment loss, against XLA's forms: the loss, the
    hidden states and the gradients of attention's leaves, the
    indexer's among them."""
    cfg_e, m_e, params, ids = tiny(attention_impl="einsum")
    m_f = keye_vl.KeyeVLLMHeadModel(
        dataclasses.replace(cfg_e, attention_impl="flash"))

    def value_and_grad(model):
        def loss(p):
            lm, aligned, _ = keye_vl_loss_parts(model, p, ids)
            return lm + aligned, (lm, aligned)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    (_, parts_e), grads_e = value_and_grad(m_e)
    with pltpu.force_tpu_interpret_mode():
        (_, parts_f), grads_f = value_and_grad(m_f)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: keye_vl_step_loss(m_f, p, ids)))(params)
    for a, b in zip(parts_f, parts_e):
        assert float(a) == pytest.approx(float(b), rel=2e-5)
    kernels = set()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.add(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert {"hvd_dsa_select", "hvd_flash_fwd_selected",
            "hvd_flash_bwd_selected", "hvd_dsa_indexer_loss"} <= kernels
    for layer in ("layer_0", "layer_1"):
        for name, want in flatten_dict(grads_e[layer]["attention"],
                                       sep="/").items():
            want = np.asarray(want)
            np.testing.assert_allclose(
                np.asarray(flatten_dict(grads_f[layer]["attention"],
                                        sep="/")[name]),
                want, rtol=1e-3, atol=1e-4 * float(np.abs(want).max()),
                err_msg=layer + "/" + name)


def _tables_before(seq, head_dim, theta):
    """``rotary_tables`` as it was before it took sections."""
    inverse = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inverse
    return jnp.cos(angles), jnp.sin(angles)


# What every caller asks for: Granite, LFM2, DeepSeek-V3's rotary part,
# Qwen3-Next's rotated quarter of a head, AFMoE, and this family's two
# (a head in sections, the indexer's one stream), tiny and published.
@pytest.mark.parametrize("seq,head_dim,theta", [
    (64, 16, 10000.0), (4096, 64, 1e7), (4096, 64, 1e6), (8192, 64, 1e4),
    (8192, 64, 1e7), (16384, 128, 1e4), (16384, 128, 1e7), (16384, 64, 1e7)])
def test_one_streams_tables_are_bit_for_bit_what_they_were(seq, head_dim,
                                                           theta):
    want = _tables_before(seq, head_dim, theta)
    for got in (rotary_tables(seq, head_dim, theta),
                rotary_tables(seq, head_dim, theta,
                              (head_dim // 8, head_dim // 4, head_dim // 8))):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_frequency_pair_reads_its_sections_stream():
    cos, sin = rotary_tables(64, 16, 1e7, (2, 3, 3), STREAMS)
    inverse = 1e7 ** (-np.arange(0, 16, 2, dtype=np.float32) / 16)
    stream = [0, 0, 1, 1, 1, 2, 2, 2]
    for pair in range(8):
        angle = np.asarray(STREAMS)[stream[pair]] * inverse[pair]
        np.testing.assert_allclose(np.asarray(cos)[:, pair], np.cos(angle),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(sin)[:, pair], np.sin(angle),
                                   rtol=1e-6, atol=1e-6)
    equal = jnp.broadcast_to(jnp.arange(64.0), (3, 64))
    for a, b in zip(rotary_tables(64, 16, 1e7, (2, 3, 3), equal),
                    rotary_tables(64, 16, 1e7)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    with pytest.raises(ValueError, match="position streams"):
        rotary_tables(64, 16, 1e7, (4, 4), STREAMS)


@pytest.mark.parametrize("bad,match", [
    (dict(num_key_value_heads=3), "must divide"),
    (dict(first_expert=6), "lie among num_experts"),
    (dict(mrope_section=(2, 3, 4)), "frequency pairs a stream"),
    (dict(indexer_head_dim=7), "pair a head's halves"),
    (dict(topk=0), "one key at least")])
def test_config_refuses_what_the_model_cannot_build(bad, match):
    with pytest.raises(ValueError, match=match):
        keye_vl.keye_vl_tiny_config(**bad)


def test_the_published_stack_by_default():
    cfg = keye_vl.KeyeVLConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) \
        == (48, 2048, 151936)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 4, 128)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.topk) \
        == (16, 64, 2048)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (128, 8, 768)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7


def test_gauges_and_scopes_of_the_step():
    cfg = keye_vl.keye_vl_tiny_config(remat=True)
    mesh = build_mesh({"dp": 2}, jax.devices()[:2])
    init_fn, step_fn, _ = make_keye_vl_train_step(cfg, mesh)
    ids = jnp.zeros((2, 64), jnp.int32)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    text = step_fn.lower(*state, ids).as_text(debug_info=True)
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_dsa_topk"] == 24
    assert gauges["hvd_dsa_pairs"] == {"which=selected": 1260.0,
                                       "which=causal": 2080.0}
    assert gauges["hvd_dsa_select_bytes"] == 2 * 64 * 4 + 64 * 4
    assert gauges["hvd_attention_kv_repeat"] == 2
    assert gauges["hvd_moe_experts"] == {"which=total": 8.0,
                                         "which=held": 4.0}
    kept = gauges["hvd_remat_kept_bytes"]
    assert kept["family=keye_vl,names=" + "+".join(keye_vl.REMAT_NAMES)] \
        == keye_vl.remat_bytes(keye_vl.REMAT_NAMES, 1, 64, cfg)
    for scope in ("attention/query", "attention/qk_norm", "attention/rotary",
                  "attention/indexer", "attention/select",
                  "attention/indexer_loss", "attention/out", "moe/router",
                  "moe/experts"):
        assert scope in text, scope


def test_remat_bytes_by_hand_at_the_published_widths():
    cfg = keye_vl.KeyeVLConfig(num_hidden_layers=6, experts_held=16)
    per_token = {
        "flash_out": 6 * 32 * 128 * 2, "flash_lse": 6 * 32 * 4,
        dsa.SELECTED_NAME: 6 * 16384 // 8, dsa.LSE_NAME: 6 * 4,
        dsa.GRADS_NAME: 6 * ((1024 + 64) * 2 + 16 * 4),
        "moe_chosen": 6 * 8 * 4}
    assert keye_vl.KEPT_NAMES == tuple(per_token)
    assert keye_vl.remat_bytes(keye_vl.KEPT_NAMES, 1, 16384, cfg) \
        == 16384 * sum(per_token.values())
    assert keye_vl.remat_bytes((keye_vl.ATTENTION_IN_NAME,), 1, 16384, cfg) \
        == 16384 * 6 * 2 * 128 * (32 + 2 * 4)


def test_initial_weights_leave_the_experts_loads_even():
    """The embedding at a standard deviation of 1 and the two matrices
    that write into the residual stream at ``1 / sqrt(2 x init_depth)``
    of ``lecun_normal``'s scale: the routers of every layer then see
    tokens that differ, and the pairs that fall on a quarter of the
    experts are a quarter of all to a few per cent, where Flax's
    defaults (held here by scaling the same draws back) send every token
    to the same few experts from the second layer on."""
    from horovod_tpu.models.layers import counts_by_expert
    cfg = keye_vl.keye_vl_tiny_config(
        hidden_size=128, num_hidden_layers=4, num_experts=32,
        num_experts_per_tok=4, experts_held=8, vocab_size=4096,
        dtype=jnp.float32)
    assert cfg.init_depth == 48 and cfg.residual_scale == 96 ** -0.5
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 512), 0,
                             cfg.vocab_size)
    params = jax.jit(keye_vl.KeyeVLLMHeadModel(cfg).init)(
        jax.random.PRNGKey(4), ids)["params"]
    attention = params["layer_0"]["attention"]
    assert float(params["word_embeddings"]["embedding"].std()) \
        == pytest.approx(1.0, rel=0.02)
    assert float(attention["out"]["kernel"].std()) == pytest.approx(
        (4 * 16) ** -0.5 * 96 ** -0.5, rel=0.05)
    assert float(attention["query"]["kernel"].std()) == pytest.approx(
        128 ** -0.5, rel=0.05)
    assert float(params["layer_0"]["moe"]["down"].std()) == pytest.approx(
        32 ** -0.5 * 96 ** -0.5, rel=0.05)
    assert float(params["layer_0"]["moe"]["up"].std()) == pytest.approx(
        128 ** -0.5, rel=0.05)

    def loads(params):
        chosen = jax.jit(lambda p: keye_vl.expert_choices(cfg, p, ids))(
            params)
        counts = [counts_by_expert(c, 32) for _, c in sorted(chosen.items())]
        return ([float(c.max() / c.mean()) for c in counts],
                [float(c[:8].sum()) / (512 * 4 / 4) for c in counts])
    fullest, held = loads(params)
    assert max(fullest) < 2.5 and all(0.8 < h < 1.2 for h in held), (
        fullest, held)
    defaults = jax.tree.map(lambda a: a, params)
    defaults["word_embeddings"]["embedding"] = \
        params["word_embeddings"]["embedding"] * 4096 ** -0.5
    for i in range(4):
        layer = defaults["layer_%d" % i]
        layer["attention"]["out"]["kernel"] = \
            layer["attention"]["out"]["kernel"] * 96 ** 0.5
        layer["moe"]["down"] = layer["moe"]["down"] * 96 ** 0.5
    fullest, _ = loads(defaults)
    assert max(fullest[1:]) > 4.0, fullest
