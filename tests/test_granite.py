"""The Granite hybrid (Mamba-2 beside grouped-query attention): the
chunked recurrence against a position-by-position one, the scaled and
chunked loss, the step's gauges, and the step on a dp x tp mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import gpt, granite, layers
from horovod_tpu.ops import ssd
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.training import granite_step_loss, make_granite_train_step


def sequential_scan(x, dt, a, b, c):
    """``S_t = exp(a dt_t) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t
    C_t``, one position at a time in float64 numpy."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    batch, seq, heads, head_dim = x.shape
    state = np.zeros((batch, heads, head_dim, b.shape[-1]))
    y = np.zeros(x.shape)
    for t in range(seq):
        state = (np.exp(a * dt[:, t])[..., None, None] * state
                 + np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], b[:, t]))
        y[:, t] = np.einsum("bhpn,bn->bhp", state, c[:, t])
    return y


def _scan_inputs(seq, seed=0, batch=2, heads=4, head_dim=8, state=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (batch, seq, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0,
                                    maxval=2.5))
    b = jax.random.normal(keys[3], (batch, seq, state))
    c = jax.random.normal(keys[4], (batch, seq, state))
    return x, dt, a, b, c


@pytest.mark.parametrize("seq,chunk", [
    (64, 8), (64, 16), (64, 64), (50, 16), (12, 256)],
    ids=["chunk8", "chunk16", "chunk64", "ragged-padded", "shorter-than-chunk"])
def test_chunked_recurrence_equals_the_sequential_one(seq, chunk):
    """Whatever the chunk length.  A sequence the chunk does not divide
    is PADDED at its end (``dt`` = 0 there: the state neither decays
    nor grows), not refused; one shorter than the chunk is one chunk."""
    args = _scan_inputs(seq)
    count, length = ssd.chunks_of(seq, chunk)
    assert count * length >= seq > (count - 1) * length
    with jax.default_matmul_precision("highest"):
        got = jax.jit(ssd.ssd_chunked, static_argnums=5)(*args, chunk)
    want = sequential_scan(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_chunked_recurrence_gradients_do_not_depend_on_the_chunk():
    """Every input's gradient at chunk 8 equals that at one chunk of
    the whole sequence; none is nan (the decay above the diagonal is
    masked before its exponential, where it would overflow)."""
    x, dt, a, b, c = _scan_inputs(32, seed=1)
    dt = dt * 4.0   # e^-400 over a chunk: an unmasked exp(+400) overflows

    def total(chunk):
        def f(*args):
            return (ssd.ssd_chunked(*args, chunk) ** 2).sum()
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
                x, dt, a, b, c)
    for small, whole in zip(total(8), total(32)):
        small, whole = np.asarray(small), np.asarray(whole)
        assert np.isfinite(small).all()
        assert np.linalg.norm(small - whole) <= 2e-3 * np.linalg.norm(whole)


def test_scan_bytes_counts_the_arrays_by_hand():
    # 2 x 4096 at the published sizes, chunk 256, bf16: the decay and
    # the weights [2, 64, 16, 256, 256] in 4 + 2 bytes, the C . B
    # scores [2, 16, 256, 256] fp32, two sets of states [2, 16, 64, 64,
    # 128] fp32.
    square = 2 * 16 * 256 * 256
    want = square * 64 * 6 + square * 4 + 2 * (2 * 16 * 64 * 64 * 128) * 4
    assert ssd.scan_bytes(2, 4096, 64, 64, 128, 256, 2) == want
    assert ssd.chunks_of(4096, 256) == (16, 256)


@pytest.mark.parametrize("scale", [1.0, 0.125])
def test_chunked_loss_scales_the_logits(scale):
    """``chunked_lm_loss(..., logits_scale=s)`` is ``lm_loss`` of the
    tied head's logits times ``s``, in value and both gradients."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    # 32 sequences walk 64 positions a chunk: three ragged chunks here.
    hidden = jax.random.normal(keys[0], (32, 2 * 64 + 7, 32))
    assert layers.loss_chunks(seq=135, sequences=32) == (3, 45)
    table = jax.random.normal(keys[1], (96, 32))
    ids = jax.random.randint(keys[2], hidden.shape[:2], 0, 96)

    def chunked(h, e):
        return layers.chunked_lm_loss(h, e, ids, logits_scale=scale)

    def plain(h, e):
        return gpt.lm_loss(jnp.einsum("bsh,vh->bsv", h, e) * scale, ids)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, table)
        want, want_g = jax.value_and_grad(plain, argnums=(0, 1))(hidden,
                                                                  table)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-7)


def test_config_refuses_what_the_model_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        granite.GraniteConfig(layer_types=("mamba", "moe"))
    with pytest.raises(ValueError, match="num_key_value_heads"):
        granite.GraniteConfig(num_attention_heads=4, num_key_value_heads=3)


def test_published_initialisation_of_the_recurrence():
    cfg = granite.granite_tiny_config(mamba_n_heads=64, mamba_d_head=2)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = granite.GraniteLMHeadModel(cfg).init(
        jax.random.PRNGKey(0), ids)["params"]
    mamba = params["layer_0"]["mamba"]
    decay_rate = np.exp(np.asarray(mamba["A_log"]))
    assert (decay_rate >= 1.0).all() and (decay_rate <= 16.0).all()
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert (np.asarray(mamba["D"]) == 1.0).all()
    assert set(params["layer_1"]) == {"attention", "mixer_norm", "mlp",
                                      "mlp_norm"}
    assert params["layer_1"]["attention"]["key"]["kernel"].shape == \
        (64, 2, 16)


def _tiny_step(axes, **config):
    cfg = granite.granite_tiny_config(dtype=jnp.float32, **config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = make_granite_train_step(cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                             cfg.vocab_size)
    return cfg, mesh, init_fn, step_fn, jax.device_put(ids, batch_sharding)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_on_dp_by_tp_equals_one_device(remat):
    """``make_granite_train_step`` under ``granite_partition_rules`` on
    dp2 x tp2: the loss the step returns is the one-device loss of the
    same parameters, and the attention and Mamba heads, the MLP's
    columns and the embedding's rows are split over ``tp``."""
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=remat)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    spec = lambda *path: tuple(jax.tree_util.tree_reduce(
        lambda t, k: t[k], path, params).sharding.spec)
    assert spec("layer_1", "attention", "key", "kernel")[1] == "tp"
    assert spec("layer_0", "mlp", "gate", "kernel")[1] == "tp"
    assert spec("layer_0", "mlp", "out", "kernel")[0] == "tp"
    assert spec("layer_0", "mamba", "out_proj", "kernel")[0] == "tp"
    assert spec("layer_0", "mamba", "A_log") == ("tp",)
    assert spec("word_embeddings", "embedding")[0] == "tp"
    assert "tp" not in spec("layer_0", "mamba", "in_proj", "kernel")
    host = jax.device_get(params)
    want = granite_step_loss(granite.GraniteLMHeadModel(cfg), host,
                             jax.device_get(ids))
    new_params, _, loss = step_fn(params, opt_state, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # AdamW moved the matrices, and decayed no vector beyond its update.
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         new_params, host)
    assert all(v > 0 for v in jax.tree.leaves(moved))
    assert moved["layer_0"]["mamba"]["A_log"] <= 1.01e-4


def test_gauges_show_in_the_metrics_snapshot():
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2})
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    step_fn.lower(*state, ids)
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_ssm_chunks"] == 4   # 64 positions in chunks of 16
    # one device's share: 2 of 4 sequences, 4 of 8 heads
    assert gauges["hvd_ssm_scan_bytes"] == ssd.scan_bytes(
        2, 64, 4, cfg.mamba_d_head, cfg.mamba_d_state, 16, 4)
    layers = gauges["hvd_hybrid_layers"]   # other families set other kinds
    assert (layers["kind=mamba"], layers["kind=attention"]) == (2.0, 1.0)


# The benchmark's cell: 8192 tokens, one period at the published
# widths, 9.27 GB of parameters and AdamW's moments, a v5e's memory.
CELL_TOKENS = 2 * 4096


def test_remat_bytes_by_hand_at_the_published_widths():
    """A token's bytes a name: gate and up are 2.68 GB at the cell's
    size, the input projection's output 1.26 GB (what the device keeps
    of them is ``test_causal_lm_families.py``'s)."""
    cfg = granite.GraniteConfig(vocab_size=12544)
    per_token = {"flash_out": 2048 * 2, "flash_lse": 32 * 4,
                 "gate_up": 10 * 2 * 8192 * 2, "in_proj": 9 * 8512 * 2}
    for name, width in per_token.items():
        assert granite.remat_bytes((name,), 2, 4096, cfg) == \
            CELL_TOKENS * width
    assert granite.remat_bytes(granite.REMAT_NAMES, 2, 4096, cfg) == \
        CELL_TOKENS * sum(per_token.values())


def test_flash_path_equals_the_einsum_path_on_grouped_heads():
    """The kernels (interpret mode, under ``jax.jit``) on keys and
    values repeated to the query heads give the grouped einsums'
    logits: 4 query heads over 2 key-value heads, scale 1/64."""
    from jax.experimental.pallas import tpu as pltpu
    cfg = granite.granite_tiny_config(dtype=jnp.float32,
                                      layer_types=("attention",))
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 32), 0,
                             cfg.vocab_size)
    einsum = granite.GraniteLMHeadModel(cfg)
    params = einsum.init(jax.random.PRNGKey(1), ids)["params"]
    flash = granite.GraniteLMHeadModel(
        dataclasses.replace(cfg, attention_impl="flash"))
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(flash.apply)({"params": params}, ids)
    want = einsum.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
