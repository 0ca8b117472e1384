"""What the GPT step holds between its forward and its backward pass
(``models/gpt.py``, ``training.make_gpt_train_step``): never the
logits, and across ``remat`` what the rule of sizes chooses.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.models import gpt, granite, layers, lfm2
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.training import gpt_step_loss, make_gpt_train_step
from test_pallas_attention import _shapes


def _value_and_grad(loss, params):
    return jax.jit(jax.value_and_grad(loss))(params)


def _rel_l2(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("sequences,dp,seq,want", [
    (16, 1, 1024, (8, 128)),     # the GPT cell: 2048 tokens, as chosen
    (2, 1, 4096, (4, 1024)),     # the Granite and LFM2 cells
    (64, 4, 1024, (8, 128)),     # 16 of the 64 on a device, not 64
    (1, 1, 8192, (4, 2048)),
    (16, 1, 301, (3, 101)),      # ragged: 2 positions of padding
    (2, 1, 1000, (1, 1000)),     # under 2048 tokens: one chunk
    (4096, 1, 16, (16, 1)),      # more sequences than that: a position
], ids=["16x1024", "2x4096", "64x1024-dp4", "1x8192", "16x301-ragged",
        "2x1000-short", "4096x16"])
def test_loss_chunks_follow_the_tokens_on_one_device(sequences, dp, seq,
                                                     want):
    """The rule on integers: the fewest chunks of at most
    ``LOSS_CHUNK_TOKENS`` tokens of ONE device, as even as the count
    allows; the global batch is divided by what the mesh shards it
    over before the rule sees it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = build_mesh({"dp": dp}, jax.devices()[:dp])
    on_one = training._sequences_on_one_device(
        NamedSharding(mesh, P("dp", None)), sequences)
    assert on_one == sequences // dp
    count, length = layers.loss_chunks(seq, on_one)
    assert (count, length) == want
    assert count * length >= seq > (count - 1) * length
    assert on_one * length <= layers.LOSS_CHUNK_TOKENS or length == 1
    # A model that is applied directly holds the whole batch.
    assert training._sequences_on_one_device(None, sequences) == sequences


# 32 sequences: 64 positions a chunk.
@pytest.mark.parametrize("seq,chunks,remat", [
    (60, (1, 60), False), (127, (2, 64), True),
    (151, (3, 51), False), (151, (3, 51), True)],
    ids=["s60-one", "s127-ragged-remat", "s151-ragged", "s151-ragged-remat"])
@pytest.mark.parametrize("masked", [False, True], ids=["", "mask"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_chunked_loss_is_lm_loss_of_the_logits(dtype, masked, seq, chunks,
                                               remat):
    """Value and the gradient of EVERY parameter leaf, against
    ``lm_loss(model(ids), ids)`` without ``remat``: float32 to 1e-5;
    bf16 no further from the float32 answer than the logits path is
    (its own rounding, the band), which the chunked loss sits inside
    because it never rounds the logits to bf16.  The names ``remat``
    keeps change no number."""
    plain = gpt.gpt_tiny_config(dtype=dtype, attention_impl="einsum",
                                max_position_embeddings=seq)
    cfg = dataclasses.replace(plain, remat=remat)
    rng = np.random.RandomState(seq)
    batch = 32
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq),
                                  dtype=np.int32))
    mask = jnp.asarray(rng.rand(batch, seq) < 0.7) if masked else None
    params = gpt.GPTLMHeadModel(plain).init(
        jax.random.PRNGKey(0), ids)["params"]
    # One whole chunk; two and three with a position and two of padding.
    assert layers.loss_chunks(seq, batch) == chunks

    def of_logits(config):
        model = gpt.GPTLMHeadModel(config)
        return lambda p: gpt.lm_loss(model.apply({"params": p}, ids), ids,
                                     mask)

    def chunked(config, **kw):
        model = gpt.GPTLMHeadModel(config, **kw)

        def loss(p):
            hidden, embedding = model.apply(
                {"params": p}, ids,
                method=gpt.GPTLMHeadModel.hidden_and_embedding)
            return layers.chunked_lm_loss(hidden, embedding, ids, mask)
        return loss

    want_loss, want = _value_and_grad(of_logits(plain), params)
    got_loss, got = _value_and_grad(chunked(cfg), params)
    # Without a gradient the primal function runs, not the VJP's
    # forward: the same value.
    np.testing.assert_allclose(jax.jit(chunked(cfg))(params), got_loss,
                               rtol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path
    # A key's bias moves no softmax: its gradient is rounding alone,
    # so absolute errors are held against the largest gradient.
    scale = max(float(jnp.abs(w).max()) for w in jax.tree.leaves(want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
        for (path, g), (_, w) in zip(leaves(got), leaves(want)):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * scale,
                                       err_msg=str(path))
    else:
        exact = dataclasses.replace(plain, dtype=jnp.float32)
        exact_loss, exact_grads = _value_and_grad(of_logits(exact), params)
        assert abs(got_loss - exact_loss) <= \
            abs(want_loss - exact_loss) + 2e-3 * abs(exact_loss)
        for (path, g), (_, w), (_, e) in zip(
                leaves(got), leaves(want), leaves(exact_grads)):
            if float(jnp.abs(e).max()) > 1e-4 * scale:
                assert _rel_l2(g, e) <= 1.25 * _rel_l2(w, e) + 2e-3, path
    if remat:
        # Kept or recomputed, the same arithmetic.
        other_loss, other = _value_and_grad(
            chunked(cfg, remat_names=gpt.FLASH_NAMES), params)
        # (bf16: XLA rounds a kept array where a recomputed one stays
        # in a fusion's float32.)
        tol = 1e-6 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(other_loss, got_loss, rtol=tol)
        for (path, g), (_, o) in zip(leaves(got), leaves(other)):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(o, np.float32),
                rtol=tol, atol=tol * scale, err_msg=str(path))


def _tiny_step(remat: bool, batch=16, seq=256):
    # Sized so that a chunk's logits (128 positions of 16 sequences) are
    # the step's largest array (the CPU's attention holds [B, heads, S,
    # S] scores).
    cfg = gpt.gpt_tiny_config(remat=remat, max_position_embeddings=seq,
                              vocab_size=2048, num_heads=2)
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    init_fn, step_fn, batch_sharding = make_gpt_train_step(cfg, mesh)
    ids = jax.device_put(jnp.zeros((batch, seq), jnp.int32), batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(0), ids)
    return cfg, step_fn, (params, opt_state, ids)


def test_gpt_step_holds_no_array_of_the_whole_logits():
    """Forward and backward, the step's jaxpr has nothing of B x S x V
    elements: two chunks here, so half of it at most.  The logits path
    does hold one: the check can see it."""
    cfg, step_fn, args = _tiny_step(remat=True)
    batch, seq = args[2].shape
    whole = batch * seq * cfg.vocab_size
    shapes = _shapes(jax.make_jaxpr(step_fn)(*args).jaxpr, set())
    assert layers.loss_chunks(seq, batch) == (2, 128)
    assert (batch, 128, cfg.vocab_size) in shapes, shapes
    assert max(map(math.prod, shapes)) * 2 <= whole, shapes

    model = gpt.GPTLMHeadModel(cfg)
    plain = jax.make_jaxpr(jax.grad(lambda p, ids: gpt.lm_loss(
        model.apply({"params": p}, ids), ids)))(args[0], args[2])
    assert (batch, seq, cfg.vocab_size) in _shapes(plain.jaxpr, set())


def test_remat_bytes_by_hand_at_the_published_widths():
    """gpt2-medium at 16 x 1024, what the benchmark's cell asks: 268 MB
    of matmul outputs a layer (what the device keeps of them is
    ``test_causal_lm_families.py``'s)."""
    cfg = gpt.gpt2_medium_config()
    assert gpt.remat_bytes(gpt.MATMUL_NAMES, 16, 1024, cfg) == \
        24 * 268_435_456
    assert gpt.remat_bytes(gpt.FLASH_NAMES, 16, 1024, cfg) == \
        24 * 16384 * (1024 * 2 + 16 * 4)


def test_gauges_show_in_the_metrics_snapshot():
    """Tracing a tiny step sets the loss's chunk count and a chunk's
    tokens (the bytes kept across ``remat`` are
    ``test_causal_lm_families.py``'s)."""
    cfg, step_fn, args = _tiny_step(remat=True, seq=3 * 128 + 5)
    step_fn.lower(*args)
    gauges = hvd.metrics_snapshot()["gauges"]
    assert "hvd_gpt_loss_chunks" not in gauges
    assert gauges["hvd_lm_loss_chunks"] == 4
    assert gauges["hvd_lm_loss_chunk_tokens"] == 16 * 98   # ceil(389 / 4)


@pytest.mark.parametrize("family", ["gpt", "granite", "lfm2"])
def test_every_builder_records_the_chunks_of_one_device(family):
    """The three causal-LM builders set both gauges when their step is
    traced, from the sequences ONE device holds: 64 sequences of 128
    positions over dp2 x tp2 are 32 a device, two chunks of 64
    positions; the global batch would walk four of 32."""
    config, make = {
        "gpt": (gpt.gpt_tiny_config(), make_gpt_train_step),
        "granite": (granite.granite_tiny_config(),
                    training.make_granite_train_step),
        "lfm2": (lfm2.lfm2_tiny_config(), training.make_lfm2_train_step),
    }[family]
    mesh = build_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    init_fn, step_fn, batch_sharding = make(config, mesh)
    ids = jax.ShapeDtypeStruct((64, 128), jnp.int32, sharding=batch_sharding)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    for gauge in (training._LOSS_CHUNKS, training._LOSS_CHUNK_TOKENS):
        gauge.set(0)   # whatever an earlier test's trace left
    text = step_fn.lower(*state, ids).as_text()
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_lm_loss_chunks"] == 2
    assert gauges["hvd_lm_loss_chunk_tokens"] == 32 * 64 == 2048
    # The walk itself: the global batch, 64 positions at a time.
    assert "tensor<64x64x%dxf32>" % config.vocab_size in text
    assert "tensor<64x32x%dxf32>" % config.vocab_size not in text


def test_step_loss_is_the_logits_loss_on_a_mesh():
    """The step's own loss function on dp2 x tp2 against the logits
    path on one device: the chunks leave the batch sharded and the
    vocabulary to GSPMD."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.sharding import (gpt_partition_rules,
                                               infer_shardings)
    cfg = gpt.gpt_tiny_config(dtype=jnp.float32, remat=True)
    mesh = build_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    model = gpt.GPTLMHeadModel(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 40), dtype=np.int32))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    want_loss, want = _value_and_grad(
        lambda p: gpt.lm_loss(model.apply({"params": p}, ids), ids), params)
    sharded = jax.device_put(
        params, infer_shardings(params, mesh, gpt_partition_rules()))
    got_loss, got = jax.jit(jax.value_and_grad(gpt_step_loss, argnums=1),
                            static_argnums=0)(
        model, sharded, jax.device_put(ids, NamedSharding(mesh, P("dp"))))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    scale = max(float(jnp.abs(w).max()) for w in jax.tree.leaves(want))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale)
