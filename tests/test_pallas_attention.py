"""Pallas flash-attention kernel correctness (interpreter mode on CPU —
the same kernel code compiles via Mosaic on TPU)."""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.common import metrics
from horovod_tpu.ops import pallas_attention
from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.parallel.attention import reference_attention

B, S, H, D = 2, 64, 2, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(qkv, causal):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, causal=causal,
                                     block_q=16, block_k=16,
                                     interpret=True))
    exp = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)


def test_flash_uneven_blocks(qkv):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, block_q=48, block_k=24,
                                     interpret=True))
    exp = np.asarray(reference_attention(q, k, v))
    np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)


@pytest.fixture(params=["fused", "split"])
def form(request, monkeypatch):
    """Both forms of the backward pass on one shape: these shapes select
    the fused kernel, and with no budget for dQ's accumulator they take
    the dK/dV kernel and the dQ kernel."""
    if request.param == "split":
        monkeypatch.setattr(pallas_attention, "FUSED_DQ_BYTES", 0)
    pallas_attention._bwd_call.clear_cache()   # whatever ran before: trace
    lowered = pallas_attention._LOWERINGS.value(form=request.param)
    yield request.param
    assert pallas_attention._LOWERINGS.value(form=request.param) > lowered


def _grads(attend, q, k, v):
    def loss(q, k, v):
        return jnp.mean(attend(q, k, v).astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _out_and_grads(attend, q, k, v):
    """``attend``'s output (its first, where it returns several, and
    then the rest beside it) and ``_grads``'s three gradients from ONE
    jitted program: the forward kernel is traced and lowered once, which
    is most of what an interpreter case costs."""
    def loss(q, k, v):
        got = attend(q, k, v)
        out = got[0] if isinstance(got, tuple) else got
        return jnp.mean(out.astype(jnp.float32) ** 2), got
    (_, got), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return got, grads


# ``test_flash_gradients_match`` of PR 21 is the first case.
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
# 32 and 26 rows: two tiles of 16 a side (and three of 12 keys under two
# of 24 queries), the smallest that still cross a tile, leave a ragged
# one and pad a block; every tile is a body traced, which is what an
# interpreter case costs.
@pytest.mark.parametrize("blocks", [(16, 16), (24, 12)],
                         ids=["16x16", "24x12"])
@pytest.mark.parametrize("seq", [32, 26], ids=["even", "ragged"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
def test_flash_backward_kernels_match_reference(qkv, causal, seq, blocks,
                                                dtype, tol, form):
    """dQ, dK, dV from the backward kernels (interpret mode; fused, and
    as two) against the gradients of the plain reference."""
    q, k, v = (t[:, :seq].astype(dtype) for t in qkv)
    got = _grads(functools.partial(
        flash_attention, causal=causal, block_q=blocks[0],
        block_k=blocks[1], interpret=True), q, k, v)
    want = _grads(functools.partial(reference_attention, causal=causal),
                  *(t.astype(jnp.float32) for t in (q, k, v)))
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype and a.shape == b.shape
        b = np.asarray(b)
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)), b, rtol=tol,
            atol=tol * float(np.abs(b).max()), err_msg="d" + name)


# 50 rows leave the last 8-wide tile ragged; 48 are whole 16-wide tiles
# and half a block of padding, which the kernels must hide all the same.
@pytest.mark.parametrize("seq,tile", [(50, (16, 8)), (48, (16, 16))],
                         ids=["ragged-tile", "whole-tiles-ragged-block"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_blocks_accumulate_across_grid_steps(qkv, causal, seq, tile,
                                                   form):
    """Sequences longer than ``seq_block``: the online softmax and the
    gradient accumulators carry over the grid's sequential dimension
    (fused, dQ over the kv blocks as dK and dV over the q blocks), the
    causal decisions are made from program ids, and keys that pad the
    last block count for nothing."""
    q, k, v = (t[:, :seq] for t in qkv)

    def attend(q, k, v):
        return pallas_attention._flash(q, k, v, D ** -0.5, causal, tile,
                                       32, True)
    ref = functools.partial(reference_attention, causal=causal)
    np.testing.assert_allclose(np.asarray(attend(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(_grads(attend, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


def test_flash_default_tiles_hide_a_padded_block():
    """The tiles and blocks the chip runs, on a sequence of whole tiles
    that fills its last block by half (1536 = 3 x 512 in blocks of
    1024), not causal: nothing but the kernels' own mask hides the
    padding."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 1536, 2, 64).astype(np.float32))
               for _ in range(3))
    attend = functools.partial(flash_attention, interpret=True)
    np.testing.assert_allclose(np.asarray(attend(q, k, v)),
                               np.asarray(reference_attention(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(_grads(attend, q, k, v),
                    _grads(reference_attention, q, k, v)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-4,
                                   atol=2e-4 * float(np.abs(b).max()))


def _shapes(jaxpr, found):
    """Every array shape in ``jaxpr`` and the jaxprs nested in it,
    kernel bodies excepted: what a ``pallas_call`` holds lives in
    VMEM."""
    for eqn in jaxpr.eqns:
        found.update(tuple(v.aval.shape) for v in eqn.outvars
                     if hasattr(v.aval, "shape"))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found)
    return found


def test_flash_gradient_holds_no_square_of_the_sequence():
    """Forward and backward: no array of rank 3 or more with two
    dimensions equal to S outside the kernels."""
    seq = 96
    x = jnp.ones((2, seq, 2, 16), jnp.float32)
    attend = functools.partial(flash_attention, causal=True, block_q=32,
                               block_k=32, interpret=True)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _grads(attend, q, k, v))(x, x, x)
    assert "pallas_call" in str(jaxpr)
    shapes = _shapes(jaxpr.jaxpr, set())
    assert (2, seq, 2 * 16) in shapes, shapes   # what the kernels are fed
    square = [s for s in shapes if len(s) >= 3 and s.count(seq) >= 2]
    assert not square, square
    # The plain path does hold one: the check can see it.
    plain = jax.make_jaxpr(lambda q, k, v: _grads(functools.partial(
        reference_attention, causal=True), q, k, v))(x, x, x)
    assert any(len(s) >= 3 and s.count(seq) >= 2
               for s in _shapes(plain.jaxpr, set()))


def test_checkpoint_policy_spares_the_forward_kernel(qkv):
    """What ``models/gpt.py`` asks of ``jax.checkpoint``: the kernels
    name their output and its row statistics, a policy that keeps those
    names leaves the recomputation no forward kernel to run, and the
    gradients are the same."""
    q, k, v = qkv
    attend = functools.partial(flash_attention, causal=True, block_q=16,
                               block_k=16, interpret=True)

    def grads(policy):
        layer = jax.checkpoint(lambda q, k, v: attend(2 * q, k, v),
                               policy=policy)
        fn = functools.partial(_grads, layer)
        return fn(q, k, v), str(jax.make_jaxpr(fn)(q, k, v))

    keep = jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse")
    (kept, kept_text), (redone, redone_text) = grads(keep), grads(None)
    assert kept_text.count("name=hvd_flash_fwd") == 1
    assert redone_text.count("name=hvd_flash_fwd") == 2
    assert kept_text.count("name=hvd_flash_bwd") == 1
    for a, b in zip(kept, redone):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _backward_kernels(budget=None, seq=64):
    """The names of the kernels in the backward pass of a [1, seq, 2,
    16] float32 attention in blocks of 32 rows (dQ's accumulator: seq x
    128 bytes), lowered with ``budget`` bytes for it."""
    x = jnp.ones((1, seq, 2 * 16), jnp.float32)
    stats = jnp.ones((1, 2, 1, seq), jnp.float32)
    budget = pallas_attention.FUSED_DQ_BYTES if budget is None else budget
    text = str(jax.make_jaxpr(functools.partial(
        pallas_attention._bwd_call, heads=2, scale=0.25, causal=True,
        tile=(16, 16), seq_block=32, interpret=True, dq_budget=budget))(
            x, x, x, stats, x, stats))
    return sorted(set(re.findall(r"name=(hvd_flash_\w+)", text)))


def test_backward_is_one_kernel_where_dq_fits_the_budget():
    """The rule reads static shapes alone: the bytes of a float32 dQ
    for a block's heads over the whole padded sequence against one
    constant.  Under it the backward lowers ``hvd_flash_bwd``, past it
    the two kernels; the gauges say which, and what the accumulator
    holds."""
    lowerings = pallas_attention._LOWERINGS
    was = {f: lowerings.value(form=f) for f in ("fused", "split")}
    assert _backward_kernels(seq=64) == ["hvd_flash_bwd"]
    assert _backward_kernels(budget=64 * 128) == ["hvd_flash_bwd"]
    gauges = metrics.snapshot()["gauges"]
    assert gauges["hvd_flash_bwd_dq_vmem_bytes"] == 64 * 2 * 16 * 4
    assert gauges["hvd_flash_bwd_lowerings"] == {
        "form=fused": was["fused"] + 2, "form=split": was["split"]}
    # One row more pads to a third block of 32: 96 x 128 bytes.
    assert _backward_kernels(budget=64 * 128, seq=65) == [
        "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]
    assert _backward_kernels(budget=96 * 128, seq=65) == ["hvd_flash_bwd"]
    assert metrics.gauge("hvd_flash_bwd_dq_vmem_bytes").value() == 96 * 128
    assert lowerings.value(form="split") == was["split"] + 1
    assert lowerings.value(form="fused") == was["fused"] + 3
    # Every shape a cell runs fits, and R3's 32k-token local blocks.
    for rows, lanes in ((8192, 384), (1024, 128), (4096, 128), (32768, 128)):
        assert rows * lanes * 4 <= pallas_attention.FUSED_DQ_BYTES


def test_flash_bf16(qkv):
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
    got = np.asarray(flash_attention(q, k, v, block_q=16, block_k=16,
                                     interpret=True).astype(jnp.float32))
    exp = np.asarray(reference_attention(q, k, v).astype(jnp.float32))
    np.testing.assert_allclose(got, exp, atol=3e-2, rtol=3e-2)


def test_bert_flash_attention_matches_einsum():
    from horovod_tpu.models.bert import (BertForMaskedLM,
                                         bert_tiny_config)
    cfg_e = bert_tiny_config(dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg_e, attention_impl="flash")
    rng = jax.random.PRNGKey(0)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg_e.vocab_size, (2, 16), dtype=np.int32))
    m_e, m_f = BertForMaskedLM(cfg_e), BertForMaskedLM(cfg_f)
    params = m_e.init(rng, ids)
    out_e = np.asarray(m_e.apply(params, ids).astype(jnp.float32))
    # The model calls the kernel compiled; off the TPU the test asks
    # Pallas for interpret mode here, by name.  That interpreter runs
    # JAX operations of its own from callbacks on XLA's threads, and
    # under load they deadlock against a main thread that dispatches
    # the model's operations one by one (PR 28: the worker then waits
    # for ever with no CPU used).  So the forward pass is one compiled
    # call, and the main thread only waits for it.
    with pltpu.force_tpu_interpret_mode():
        out_f = np.asarray(
            jax.jit(m_f.apply)(params, ids).astype(jnp.float32))
    np.testing.assert_allclose(out_f, out_e, atol=3e-2, rtol=3e-2)


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("axes", [None, {"dp": 2, "tp": 2}],
                         ids=["direct", "dp2xtp2"])
def test_gpt_flash_kernels_match_einsum(axes):
    """GPT-tiny through the kernels (forced to interpret mode) against
    the einsum path: logits, and the gradient of three leaves.  On a
    mesh of several devices the kernels run shard by shard."""
    from horovod_tpu.models.gpt import (GPTLMHeadModel, gpt_tiny_config,
                                        lm_loss)
    from horovod_tpu.parallel.mesh import build_mesh
    sharding = axes and NamedSharding(
        build_mesh(axes, jax.devices()[:4]), P("dp", None, "tp", None))
    cfg_e = gpt_tiny_config(dtype=jnp.float32, attention_impl="einsum")
    cfg_f = dataclasses.replace(cfg_e, attention_impl="flash")
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg_e.vocab_size, (4, 48), dtype=np.int32))
    m_e, m_f = GPTLMHeadModel(cfg_e), GPTLMHeadModel(
        cfg_f, heads_sharding=sharding)
    params = m_e.init(jax.random.PRNGKey(0), ids)["params"]

    def value_and_grad(model):
        def loss(p):
            logits = model.apply({"params": p}, ids)
            return lm_loss(logits, ids), logits
        (_, logits), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return logits, grads

    logits_e, grads_e = value_and_grad(m_e)
    # Under ``jax.jit`` (in ``value_and_grad``) for the reason given in
    # ``test_bert_flash_attention_matches_einsum``: this interpreter's
    # callbacks deadlock against eager dispatch.
    with pltpu.force_tpu_interpret_mode():
        logits_f, grads_f = value_and_grad(m_f)
    np.testing.assert_allclose(np.asarray(logits_f), np.asarray(logits_e),
                               atol=2e-4, rtol=2e-4)
    for path in ("layer_0/attention/query/kernel",
                 "layer_1/attention/value/kernel",
                 "word_embeddings/embedding"):
        want = _leaf(grads_e, path)
        np.testing.assert_allclose(
            _leaf(grads_f, path), want, rtol=1e-3,
            atol=1e-4 * float(np.abs(want).max()), err_msg=path)


def test_gpt_picks_its_attention_by_platform_and_dropout():
    """``attention_impl="auto"``: the kernels on a TPU where no
    attention dropout is applied (and not for ``init``), the einsums
    elsewhere.  The mesh the step builder hands over decides before
    the default backend."""
    from horovod_tpu.models.gpt import attention_impl, gpt_tiny_config
    cfg = gpt_tiny_config(dropout=0.1)
    assert cfg.attention_impl == "auto"
    assert jax.default_backend() == "cpu"
    assert attention_impl(cfg, None, kernels_apply=True) == "einsum"

    class Device:
        platform = "tpu"

    class OnTpu:
        devices = np.array([Device()])

    assert attention_impl(cfg, OnTpu(), kernels_apply=True) == "flash"
    assert attention_impl(cfg, OnTpu(), kernels_apply=False) == "einsum"
    named = dataclasses.replace(cfg, attention_impl="flash")
    assert attention_impl(named, None, kernels_apply=True) == "flash"


def test_flash_compiled_is_refused_off_tpu(qkv):
    """No quiet interpret default: off the TPU the compiled kernel
    raises instead of running some other code in its place."""
    q, k, v = qkv
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, k, v)


# Values narrower than keys (latent attention: a positional part rides
# on queries and keys alone).  Neither case has a count of heads under
# all of them that fills whole lanes at both widths, so a block takes
# them all: 4 heads of 24 and 16, 6 heads of 64 and 32.
# Two heads at each pair of widths: of neither does a count fill the
# lanes at both (``test_a_block_holds_the_same_heads_of_both_widths``
# has the counts), so a block holds every head, as it held four and six.
@pytest.mark.parametrize("heads,d,dv", [(2, 24, 16), (2, 64, 32)],
                         ids=["24-16", "64-32"])
@pytest.mark.parametrize("seq,seq_block", [(32, 32), (50, 32)],
                         ids=["one-block", "ragged-blocks"])
def test_flash_values_narrower_than_keys(heads, d, dv, seq, seq_block, form):
    """Forward and the three gradients against the einsum, causal,
    jitted: the output and dV take the values' width, dQ and dK the
    queries', and the scale comes from the queries' width."""
    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(2, seq, heads, d).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, seq, heads, dv).astype(np.float32))

    def attend(q, k, v):
        return pallas_attention._flash(q, k, v, d ** -0.5, True, (16, 16),
                                       seq_block, True)
    ref = functools.partial(reference_attention, causal=True)
    got, got_g = _out_and_grads(attend, q, k, v)
    assert got.shape == (2, seq, heads, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", got_g, _grads(ref, q, k, v)):
        assert a.shape == b.shape, name
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-4,
                                   atol=2e-5 * float(np.abs(b).max()),
                                   err_msg="d" + name)


def test_flash_default_scale_is_the_queries_width():
    rng = np.random.RandomState(4)
    q, k = (jnp.asarray(rng.randn(1, 32, 2, 24).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 32, 2, 16).astype(np.float32))
    attend = jax.jit(functools.partial(flash_attention, causal=True,
                                       block_q=16, block_k=16,
                                       interpret=True))
    np.testing.assert_allclose(
        np.asarray(attend(q, k, v)),
        np.asarray(reference_attention(q, k, v, causal=True,
                                       scale=24 ** -0.5)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,d,dv,g", [
    (16, 64, 64, 2), (32, 192, 128, 2), (16, 128, 128, 1), (4, 24, 16, 4),
    (12, 64, 64, 2), (32, 64, 128, 2), (2, 24, 16, 2), (2, 64, 32, 2),
    (6, 64, 32, 6)],
    ids=["gpt2", "latent", "d128", "toy", "gpt2-small", "wider-values",
         "two-toys", "two-halves", "six-halves"])
def test_a_block_holds_the_same_heads_of_both_widths(heads, d, dv, g):
    assert pallas_attention._heads_per_block(heads, d, dv) == g


def _window_reference(q, k, v, window):
    """The einsum path's own mask (``models/layers.py``
    ``visible_keys``) on plain full scores."""
    from horovod_tpu.models.layers import visible_keys
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(visible_keys(q.shape[1], window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


# (sequence, tile, rows a block, window): a window under a tile, a
# block's length, longer than the sequence, one key (the query's own),
# a sequence that is no multiple of the tile, a band that needs three
# blocks, and one a tile long over blocks of one tile.
WINDOWS = [(64, 16, 32, 8), (64, 16, 32, 32), (64, 16, 32, 100),
           (48, 16, 32, 1), (50, 16, 32, 20), (96, 16, 32, 33),
           (64, 16, 16, 17)]


# Two heads of 24 | 16: the second head's lanes start off a tile at both
# widths, and no count of heads fills the lanes, so a block holds both.
@pytest.mark.parametrize("heads,d,dv", [(2, 16, 16), (2, 24, 16)],
                         ids=["one-width", "values-narrower"])
@pytest.mark.parametrize("seq,tile,seq_block,window", WINDOWS, ids=[
    "under-a-tile", "a-block", "over-the-sequence", "own-key-alone",
    "ragged-sequence", "three-blocks", "a-tile-over-small-blocks"])
def test_flash_window_matches_the_einsum_mask(seq, tile, seq_block, window,
                                              heads, d, dv, form):
    """A causal call with a window, forward and all three gradients
    (fused, and as two kernels): the band's walk over ``band_steps``
    blocks, its clamped steps skipped, tiles left of the band skipped
    and the two edges masked, against plain scores under the einsum
    path's mask."""
    rng = np.random.RandomState(5)
    q, k = (jnp.asarray(rng.randn(2, seq, heads, d).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, seq, heads, dv).astype(np.float32))

    def attend(q, k, v):
        return pallas_attention._flash(q, k, v, d ** -0.5, True,
                                       (tile, tile), seq_block, True, window)
    ref = functools.partial(_window_reference, window=window)
    got, got_g = _out_and_grads(attend, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", got_g, _grads(ref, q, k, v)):
        b = np.asarray(b)
        # one key alone: dQ is 0 and the kernels' a rounding of it
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=2e-4,
            atol=2e-5 * max(float(np.abs(b).max()), 1e-3),
            err_msg="d" + name)


def test_a_window_call_is_named_and_walks_the_band_alone():
    """The window call's kernels carry their own names, the grid's last
    dimension has the band's steps and not the sequence's blocks, and a
    call with no window is the one it was."""
    q = jnp.zeros((1, 64, 2, 16))

    def kernels(window):
        jaxpr = jax.make_jaxpr(functools.partial(_grads, lambda q, k, v: (
            pallas_attention._flash(q, k, v, 0.25, True, (16, 16), 16, True,
                                    window))))(q, q, q)
        found = {}

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    found[eqn.params["name"]] = \
                        eqn.params["grid_mapping"].grid
                    continue
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found
    assert kernels(None) == {"hvd_flash_fwd": (1, 1, 4, 4),
                             "hvd_flash_bwd": (1, 1, 4, 4)}
    # 17 keys back reach the block before a query's own: two steps
    assert kernels(17) == {"hvd_flash_fwd_window": (1, 1, 4, 2),
                           "hvd_flash_bwd_window": (1, 1, 4, 2)}
    # 16 back still reach it from a block's first query; one key alone
    # is the query's own
    assert kernels(16)["hvd_flash_fwd_window"] == (1, 1, 4, 2)
    assert kernels(1)["hvd_flash_fwd_window"] == (1, 1, 4, 1)
    assert kernels(1000)["hvd_flash_bwd_window"] == (1, 1, 4, 4)
    assert pallas_attention.band_steps(2048, 1024, 16) == 3
    assert pallas_attention.band_steps(2049, 1024, 16) == 3
    assert pallas_attention.band_steps(2050, 1024, 16) == 4
    assert pallas_attention.band_steps(1, 1024, 16) == 1


@pytest.mark.parametrize("seq,window,tile", [
    (16384, 2048, 512), (8192, 2048, 512), (4096, 512, 512),
    (2048, 4096, 512), (1024, 700, 512), (64, 8, 16)])
def test_the_bands_tiles_by_a_formula(seq, window, tile):
    """``band_tiles`` (the kernels' own rule, tile by tile) against a
    count by rows of tiles: row ``a`` walks the tiles from the one that
    holds its first query's oldest key to its own, masks its own and,
    where the oldest key of its LAST query lies past a tile's first key,
    that tile, and skips what the triangle has beside them."""
    got = pallas_attention.band_tiles(seq, window, (tile, tile))
    rows = -(-seq // tile)
    walked = masked = 0
    for a in range(rows):
        first = max(0, (a * tile - (window - 1)) // tile)
        walked += a - first + 1
        edge = {c for c in range(first, a + 1)
                if c * tile <= a * tile + tile - 1 - window}
        masked += len(edge | {a})
    triangle = rows * (rows + 1) // 2
    assert (got["walked"], got["masked"], got["skipped"]) == \
        (walked, masked, triangle - walked)
    inside = min(window, seq)
    assert got["fill"] == pytest.approx(
        (inside * (inside + 1) / 2 + (seq - inside) * inside)
        / (walked * min(tile, seq) ** 2))
    if (seq, window) == (16384, 2048):
        assert (walked, masked, triangle) == (150, 60, 528)
        assert 0.80 < got["fill"] < 0.8001


@pytest.mark.parametrize("bad,match", [
    (dict(causal=False, window=8), "causal"),
    (dict(causal=True, window=0), "own position"),
    (dict(causal=True, window=8, block_q=16, block_k=32), "blocks")],
    ids=["not-causal", "no-key", "blocks-of-two-sizes"])
def test_a_window_call_refuses_what_it_cannot_walk(qkv, bad, match):
    # 40 rows: three tiles of 16 (a block of 48), two of 32 (one of 64)
    q, k, v = (t[:, :40] for t in qkv)
    with pytest.raises(ValueError, match=match):
        jax.jit(functools.partial(flash_attention, interpret=True,
                                  **bad))(q, k, v)


def _a_selection(seq, topk, tile, seed=3):
    """A selection as an indexer makes it (``ops/dsa.py``): ``topk`` of a
    query's causal keys, every one of them where there are no more."""
    from horovod_tpu.ops import dsa
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    packed, _ = dsa.select(jax.random.normal(ks[0], (2, seq, 3, 8)),
                           jax.random.normal(ks[1], (2, seq, 8)),
                           jax.random.normal(ks[2], (2, seq, 3)), topk,
                           key_tile=tile)
    return packed, dsa.unpack_mask(packed, tile)


# 96 rows in blocks of 64 and tiles of 32: a padded block, three tiles of
# keys, and the 40th key in the second tile, so that the first tile of
# queries is the causal call's and the rest read the bits.
@pytest.mark.parametrize("seq,tile,seq_block,topk", [
    (96, 32, 64, 40), (64, 32, 64, 200)],
    ids=["a-block-a-tile-the-topkth-key", "every-query-keeps-all"])
def test_flash_selected_matches_the_einsum_mask(seq, tile, seq_block, topk):
    """A call with a selection, forward, the rows' log-sum-exp and all
    three gradients, against plain scores under the unpacked mask; one
    selection serves every head."""
    heads, d = 2, 16
    packed, keep = _a_selection(seq, topk, tile)
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, seq, heads, d).astype(np.float32))
               for _ in range(3))
    assert int(keep[0, -1].sum()) == min(topk, seq)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        s = jnp.where(keep[:, None], s, -jnp.inf)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))

    def attend(q, k, v):
        return pallas_attention._flash_selected(
            q, k, v, packed, d ** -0.5, (tile, tile), seq_block, True, topk)
    (got, got_lse), got_g = _out_and_grads(attend, q, k, v)
    want, want_lse = plain(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", got_g,
                          _grads(lambda *a: plain(*a)[0], q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5 * float(np.abs(b).max()),
                                   err_msg="d" + name)


def test_a_selected_call_is_named_and_a_plain_call_is_what_it_was():
    """The kernels of a call with a selection carry their own names and
    one operand more, the packed mask's block; a call without has the
    operands it had."""
    q = jnp.zeros((1, 64, 2, 16))
    packed = jnp.zeros((1, 2, 64), jnp.int32)

    def kernels(fn):
        found = {}

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    found[eqn.params["name"]] = len(eqn.invars)
                    continue
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(functools.partial(_grads, fn))(q, q, q).jaxpr)
        return found
    assert kernels(lambda q, k, v: pallas_attention._flash(
        q, k, v, 0.25, True, (32, 32), 32, True)) == {
            "hvd_flash_fwd": 3, "hvd_flash_bwd": 6}
    assert kernels(lambda q, k, v: pallas_attention._flash_selected(
        q, k, v, packed, 0.25, (32, 32), 32, True, 40)[0]) == {
            "hvd_flash_fwd_selected": 4, "hvd_flash_bwd_selected": 7}
    with pytest.raises(ValueError, match="fused backward alone"):
        jax.eval_shape(functools.partial(
            pallas_attention._bwd_call, heads=2, scale=0.25, causal=True,
            tile=(32, 32), seq_block=32, interpret=True, dq_budget=0,
            topk=40), *(jnp.zeros((1, 64, 32)),) * 3,
            jnp.zeros((1, 2, 1, 64)), jnp.zeros((1, 64, 32)),
            jnp.zeros((1, 2, 1, 64)), packed)


def test_the_selected_calls_tiles_by_hand():
    # 32 tiles of 512 a side: 528 on or under the diagonal; all but the
    # 6 under it among the first 2048 queries read the bits or the
    # diagonal.
    assert pallas_attention.selected_tiles(16384, 2048) == {
        "walked": 528, "masked": 522, "skipped": 0}
    assert pallas_attention.selected_tiles(96, 40, (32, 32), 64) == {
        "walked": 10, "masked": 10, "skipped": 0}
