"""The Granite hybrid family in the benchmark: the program against the
plain reference at a tiny size, the comparison that decides ``correct``,
the FLOP count by hand, the vocabulary's shares against the uncut model,
the in-graph trainer on a tiny cell, and the two readers it brings."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import _write, make_root, spec
from benchmarks.reference import common as reference
from benchmarks.reference import granite as granite_reference

FAMILY = "granite"
# Two Mamba layers around one attention layer, 4 query heads over 2
# key-value heads; sequence 64 in chunks of 16.
TINY = dict(hidden_size=64, shared_intermediate_size=128,
            intermediate_size=128, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=16, vocab_size=512,
            check_leaves=["layer_0/mamba/in_proj/kernel",
                          "layer_0/mamba/A_log",
                          "layer_1/attention/key/kernel"])


def real_config():
    """``(name, config)`` of the family's first real configuration."""
    directory = os.path.join(spec.HERE, "configs")
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name)) as f:
            config = json.load(f)
        if config["family"] == FAMILY:
            return name[:-5], config
    raise KeyError(FAMILY)


def tiny_config(**over):
    return {**real_config()[1], **TINY, **over}


def _case(dtype, seed=0, batch=2, seq=64, **over):
    config = tiny_config(compute_dtype=dtype, **over)
    family = spec.load_family(config)
    data = family.host_batch(config, batch, seq, np.random.default_rng(seed))
    params = family.init_params(config, jax.random.PRNGKey(seed), data)
    return config, family, params, data


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_program_equals_the_reference_in_float32(remat):
    """The step's own loss (chunked recurrence in four chunks, grouped
    heads, the scaled head over chunks of the sequence) and every
    gradient leaf against the position-by-position reference."""
    config, family, params, data = _case("float32", remat=remat)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(family.system_loss(config))(
            params, data)
        want, want_g = jax.value_and_grad(family.reference_loss(config))(
            params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got, flat_want = jax.tree.leaves(got_g), jax.tree.leaves(want_g)
    assert len(flat_got) == len(flat_want) == 37
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6 * float(np.abs(w).max() + 1))


@pytest.mark.parametrize("seed,over", [
    (2, {}), (1, {"residual_multiplier": 1.0})],
    ids=["published-multipliers", "residual-multiplier-1"])
def test_comparison_passes_bf16_and_fails_fp8(seed, over):
    """As the two other families' test.  Three layers that each add
    0.22 of their output damp a rounding's reach: with the published
    multiplier fp8 weights fail by one leaf on this seed (``A_log``,
    0.25 against 0.1) and stay just inside on others (0.07 to 0.09
    against bf16's 0.01); with the multiplier at 1 they fail by all
    three.  What the chip shows at the published widths is in PERF.md."""
    config, family, params, data = _case("bfloat16", seed=seed, **over)
    leaves = config["check_leaves"]
    system, ref = family.system_loss(config), family.reference_loss(config)
    ok, report = reference.compare(system, ref, params, data, leaves)
    assert ok, report
    assert set(report["grad_rel_l2"]) == set(leaves)

    def rounded(p, batch):
        # Weights rounded to 3 bits of mantissa before use: what fp8
        # matmul inputs would do.
        p = jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a), p)
        return system(p, batch)
    ok, report = reference.compare(rounded, ref, params, data, leaves)
    assert not ok, report


def test_reference_scan_blocks_do_not_change_its_result(monkeypatch):
    """The reference's recurrence in checkpointed blocks of 64, of 7
    (which 64 positions do not divide: padded with ``dt`` = 0) and in
    one block gives one loss."""
    config, family, params, data = _case("float32")
    ref = family.reference_loss(config)
    with jax.default_matmul_precision("highest"):
        want = float(ref(params, data))
        for block in (7, 1 << 20):
            monkeypatch.setattr(granite_reference, "SCAN_BLOCK", block)
            assert float(ref(params, data)) == pytest.approx(want, rel=1e-6)


def test_flops_by_hand_for_2_by_4096():
    name, config = real_config()
    family = spec.load_family(config)
    assert config["num_hidden_layers"] == 10
    mlp = 3 * 2 * 2048 * 8192
    mamba = (2 * 2048 * (4096 + 4352 + 64) + 2 * 4096 * 2048   # projections
             + 2 * 4 * 4352                                    # convolution
             + 2 * 128 * 128 + 2 * 128 * 4096                  # within a chunk
             + 2 * 2 * 4096 * 128)                             # states in, out
    attention = (2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512
                 + 2 * 2 * 2048 * 2048)          # 2048 keys: half of 4096
    head = 2 * 2048 * 12544
    per_token = 10 * mlp + 9 * mamba + attention + head
    assert family.mamba_flops_per_token(config) == mamba
    assert family.flops_per_step(config, 2, 4096) == pytest.approx(
        3.0 * per_token * 2 * 4096, rel=1e-12)
    # The recurrence itself is 3 MFLOP of a mixer's 55; the mixers a
    # third of a Mamba layer; the head 3 % of the step.
    assert 3.1e6 < mamba - 2 * 2048 * 8512 - 2 * 4096 * 2048 < 3.3e6
    assert 0.34 < mamba / (mamba + mlp) < 0.36
    assert 0.03 < head / per_token < 0.035


def test_the_trainers_count_is_the_published_one():
    """772 M parameters: nine Mamba layers of 76.18 M, one attention
    layer of 60.82 M, an eighth of the embedding and the final norm."""
    name, config = real_config()
    family = spec.load_family(config)
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: family.init_params(config, key, {"input_ids": jnp.zeros(
            ids.shape, ids.dtype)}), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    mamba = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048)
    mlp = 2048 * 16384 + 8192 * 2048
    assert count(shapes["layer_0"]) == mamba + mlp + 2 * 2048 == 76_182_976
    assert count(shapes["layer_5"]) == 60_821_504
    assert count(shapes) == 9 * 76_182_976 + 60_821_504 \
        + 12544 * 2048 + 2048 == 772_160_448
    assert [k for k in shapes["layer_5"]] == ["attention", "mixer_norm",
                                              "mlp", "mlp_norm"]


def test_vocabulary_shares_add_up_to_the_uncut_model():
    """The cut is the chip's share of a stated deployment: the eight
    models that hold an eighth of the embedding's rows each give, side
    by side, the uncut reference's logits; and the configuration's
    ``reduced``, its published values and the manifest agree."""
    name, config = real_config()
    entry = next(c for c in spec.load_manifest()["configs"]
                 if c["name"] == name)
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                     "vocab_size"]
    published = config["published"]
    assert published == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert len(config["layer_types"]) == published["num_hidden_layers"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    period = config["layer_types"][:config["num_hidden_layers"]]
    assert config["layer_types"] == period * 4   # one whole period held
    assert "8 data-parallel chips" in config["deployment"]
    assert entry["source"] == config["source"]

    # Each share on tokens of its own rows (a token whose row lies on
    # another chip needs the exchange of a row-sharded embedding, which
    # a one-chip cell leaves out, in the program and the reference
    # alike): share k's logits are the uncut model's on the same tokens,
    # over rows k.
    from horovod_tpu.models.granite import GraniteLMHeadModel
    shares, held = 8, 512 // 8
    uncut, family, params, _ = _case("float32", vocab_size=512)
    cut = dict(uncut, vocab_size=held)
    model = GraniteLMHeadModel(family.program_config(cut))
    rows = params["word_embeddings"]["embedding"]
    rng = np.random.default_rng(0)
    got, want = [], []
    with jax.default_matmul_precision("highest"):
        for k in range(shares):
            ids = rng.integers(0, held, (2, 64), dtype=np.int32)
            share = dict(params, word_embeddings={
                "embedding": rows[k * held:(k + 1) * held]})
            got.append(model.apply({"params": share}, ids))
            whole = granite_reference.logits(
                params, {"input_ids": ids + k * held}, uncut)
            want.append(whole[..., k * held:(k + 1) * held])
            # and the share's loss is the reference's over the slice
            assert float(family.system_loss(cut)(
                share, {"input_ids": ids})) == pytest.approx(float(
                    granite_reference.loss(share, {"input_ids": ids}, cut)),
                    rel=2e-6)
    got, want = jnp.concatenate(got, -1), jnp.concatenate(want, -1)
    assert got.shape == want.shape == (2, 64, 512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def make_granite_root(root: str, **traffic) -> str:
    """One tiny cell of the family under ``root`` (``benchmark_tiny``'s
    ``make_root`` knows the two families it was written with)."""
    name = make_root(root, "gpt", "ingraph", **traffic)   # the mix, the links
    _write(tiny_config(), root, "configs", FAMILY + "-tiny.json")
    cell = "%s-tiny_ingraph" % FAMILY
    _write({"config": FAMILY + "-tiny", "traffic": "ingraph_tiny", "chips": 1,
            "loss_band": {"step": 8, "low": None, "high": None}},
           root, "workloads", cell + ".json")
    os.remove(os.path.join(root, "workloads", name + ".json"))
    return cell


@pytest.mark.parametrize("trace", [0, 1])
def test_ingraph_trainer_on_a_tiny_cell(tmp_path, trace):
    from benchmarks.trainers import ingraph
    name = make_granite_root(str(tmp_path), batch_per_chip=2, seq_len=64)
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", str(2 ** 31 + 5), "--seconds", "0.5",
         "--trace", str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert run["window_compiles"] == 0
    # 2 Mamba layers, 1 attention layer, a 512-row embedding, the norm
    assert run["n_params"] == 2 * 52_792 + 36_992 + 512 * 64 + 64
    if not trace:
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0


def test_the_two_readers():
    readers = spec.metric_readers()
    ops = [["layer_*/mlp/in [mxu]", 1.0],
           ["checkpoint/layer_*/mamba/ssd/intra_chunk [loop fusion]", 0.5],
           ["layer_*/mamba/in_proj [mxu]", 0.3],
           ["rematted_computation/layer_*/mamba/gated_norm/norm [loop]", 0.1],
           ["layer_*/mamba/conv [loop fusion]", 0.1],
           ["layer_*/attention/hvd_flash_fwd [custom-call]", 0.2]]
    share = readers["ssm_share"]
    assert share.read({"trace": {"self_s": 3.5, "device_ops": ops}}) == \
        pytest.approx(100.0 * 0.7 / 3.5)
    assert share.read({"trace": {"self_s": 3.5, "device_ops": ops[:1]}}) == 0
    assert share.read({"trace": None}) is None and share.read({}) is None

    import horovod_tpu as hvd
    from horovod_tpu.common import metrics
    gib = readers["ssm_scan_gib"]
    metrics.gauge("hvd_ssm_scan_bytes").set(3 << 29)
    assert hvd.metrics_snapshot()["gauges"]["hvd_ssm_scan_bytes"] == 3 << 29
    assert gib.read({"trace": {"self_s": 1.0}}) == 1.5
    assert gib.read({}) is None
    # A program that declares no such gauge (the parent commit): nothing.
    registry = metrics.MetricsRegistry()
    registry.gauge("hvd_other")
    was, metrics.REGISTRY = metrics.REGISTRY, registry
    try:
        assert gib.read({"trace": {"self_s": 1.0}}) is None
    finally:
        metrics.REGISTRY = was
