"""The LFM2-MoE family in the benchmark: the program against the plain
reference at a tiny size, the comparison that decides ``correct`` and
what it holds the routers' choices to, the FLOP count by hand, the
experts' and the vocabulary's shares against the uncut model, the
in-graph trainer on a tiny cell, and the two readers it brings."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import _write, make_root, spec
from benchmarks.reference import common as reference
from benchmarks.reference import lfm2 as lfm2_reference

FAMILY = "lfm2"
# A dense convolution layer, then attention and convolution with routed
# experts (published layers 0, 2, 3); 4 query heads over 2 key-value
# heads; 8 experts of which 4 are held, top 2; sequence 64.
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, layers_held=[0, 2, 3], num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, num_experts=4,
            num_experts_per_tok=2, vocab_size=512)


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
    config = real_config()[1]
    published = dict(config["published"], num_experts=8)
    return {**config, **TINY, "published": published, **over}


def _case(dtype, seed=0, batch=2, seq=64, **over):
    config = tiny_config(compute_dtype=dtype, **over)
    family = spec.load_family(config)
    data = family.host_batch(config, batch, seq, np.random.default_rng(seed))
    params = family.init_params(config, jax.random.PRNGKey(seed), data)
    return config, family, params, data


@pytest.mark.parametrize("first_expert", [0, 4], ids=["first0", "first4"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_program_equals_the_reference_in_float32(remat, first_expert):
    """The step's own loss (sort-and-gather dispatch, grouped products,
    normed and rotated grouped heads, the head over chunks of the
    sequence) and every gradient leaf against the reference, whose
    sparse layer has no dispatch.  In float32 both choose alike."""
    config, family, params, data = _case("float32", remat=remat,
                                         first_expert=first_expert)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(family.system_loss(config))(
            params, data)
        want, want_g = jax.value_and_grad(family.reference_loss(config))(
            params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got, flat_want = jax.tree.leaves(got_g), jax.tree.leaves(want_g)
    assert len(flat_got) == len(flat_want) == 33
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6 * float(np.abs(w).max() + 1))
    # the selection bias selects and is not trained
    assert float(np.abs(got_g["layer_1"]["moe"]["expert_bias"]).max()) == 0


@pytest.mark.parametrize("seed", [0, 5, 6])
def test_comparison_passes_bf16_and_fails_fp8(seed):
    """As the other families' test.  bfloat16 flips a few near-ties of
    the routers' choices (1 to 3 of a layer's 256 here); the reference
    computes on the program's choice, and the three leaves read 0.02 to
    0.04 where fp8 weights read 0.3 to 0.7."""
    config, family, params, data = _case("bfloat16", seed=seed, remat=True)
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


def _report(differ, gap, choices=1000):
    return {1: {"choices": choices, "differ": jnp.int32(differ),
                "widest_gap": jnp.float32(gap)}}


@pytest.mark.parametrize("differ,gap,agree", [
    (0, 0.0, True), (45, 0.034, True), (46, 0.001, False),
    (1, 0.036, False)],
    ids=["same", "few-near-ties", "too-many", "not-a-near-tie"])
def test_choices_are_held_to_near_ties_and_a_share(differ, gap, agree):
    family = spec.load_family(tiny_config())
    assert (family.MIN_AGREEMENT, family.NEAR_TIE) == (0.955, 0.035)
    assert bool(family.choices_agree(_report(differ, gap))) is agree


def test_a_choice_that_is_no_near_tie_fails_the_comparison(monkeypatch):
    """A program that routes elsewhere (here: the choice of another
    token) has no reference loss, whatever its own loss is."""
    config, family, params, data = _case("float32")
    from horovod_tpu.models import lfm2
    honest = lfm2.expert_choices
    assert np.isfinite(float(family.reference_loss(config)(params, data)))
    monkeypatch.setattr(
        lfm2, "expert_choices",
        lambda cfg, p, ids: {i: jnp.roll(c, 7, axis=0)
                             for i, c in honest(cfg, p, ids).items()})
    assert np.isnan(float(family.reference_loss(config)(params, data)))


def test_the_check_program_holds_no_host_callback():
    """A host callback would keep it out of the compile cache."""
    config, family, params, data = _case("float32")
    text = str(jax.make_jaxpr(family.reference_loss(config))(params, data))
    assert "callback" not in text


@pytest.mark.parametrize("rounded", [False, True],
                         ids=["bfloat16", "three-bits"])
def test_the_routing_report_tells_a_rounded_program(rounded):
    """What ``init`` prints: the program's choices against the
    reference's routers.  bfloat16 moves a few near-ties; a program
    whose weights keep three bits of mantissa chooses otherwise in a
    sixteenth of a layer's pairs (16 of 256 here), and not by
    near-ties."""
    config, family, params, data = _case("bfloat16")
    program = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 8, 3), params) \
        if rounded else None
    report = family.routing_report(config, *family.routing_of(
        config, params, data["input_ids"], program))
    assert sorted(report) == [1, 2]
    assert bool(family.choices_agree(report)) is not rounded
    for r in report.values():
        assert int(r["choices"]) == 256 and r["pairs_expected"] == 128
        assert (int(r["differ"]) > 8) is rounded
    family.say(jax.device_get(report))
    import horovod_tpu as hvd
    assert set(hvd.metrics_snapshot()["gauges"]["hvd_moe_pairs_held"]) >= {
        "layer=1", "layer=2"}


def test_the_reference_takes_a_choice_and_keeps_its_own_gates():
    """Handed its own choice the reference gives its own loss, with no
    gap; handed another it computes on it, and says how far each expert
    taken lay under its own."""
    config, family, params, data = _case("float32")
    own_loss, saw = lfm2_reference.loss_and_routing(params, data, config)
    own = {i: s["own"] for i, s in saw.items()}
    assert sorted(own) == [1, 2] and own[1].shape == (128, 2)
    again, saw = lfm2_reference.loss_and_routing(params, data, config, own)
    assert float(again) == float(own_loss)
    assert all(float(s["gap"].max()) == 0.0 for s in saw.values())
    other = {1: (own[1] + 1) % 8}
    moved, saw = lfm2_reference.loss_and_routing(params, data, config, other)
    assert float(moved) != float(own_loss)
    assert float(saw[1]["gap"].max()) > 0.05 and (saw[1]["gap"] >= 0).all()


def test_flops_by_hand_for_2_by_4096():
    name, config = real_config()
    family = spec.load_family(config)
    assert config["num_hidden_layers"] == 5
    conv = (2 * 2048 * 6144 + 2 * 2048 * 2048      # in_proj, out_proj
            + 2 * 3 * 2048 + 2 * 2048)             # three taps, two gates
    attention = (2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512
                 + 2 * 2 * 2048 * 2048)          # 2048 keys: half of 4096
    dense = 3 * 2 * 2048 * 11776
    # the router over all 64, and 4 x 16 / 64 = 1.0 expert a token
    sparse = 2 * 2048 * 64 + 1.0 * 3 * 2 * 2048 * 1536
    head = 2 * 2048 * 16384
    per_token = 4 * conv + attention + dense + 4 * sparse + head
    assert family.conv_flops_per_token(config) == conv
    assert family.sparse_ffn_flops_per_token(config) == sparse
    assert family.flops_per_step(config, 2, 4096) == pytest.approx(
        3.0 * per_token * 2 * 4096, rel=1e-12)
    # 460 MFLOP a token forward, 11.3 TFLOP a step; the four sparse
    # layers 47 %, their experts' products 16 %, the dense layer 39 %
    # (its SwiGLU 31 %), the quarter head 15 %.
    assert 460e6 < per_token < 461e6
    assert 11.3e12 < family.flops_per_step(config, 2, 4096) < 11.35e12
    sparse_layers = attention + 3 * conv + 4 * sparse
    assert 0.46 < sparse_layers / per_token < 0.47
    assert 0.16 < 4 * (sparse - 2 * 2048 * 64) / per_token < 0.17
    assert 0.38 < (conv + dense) / per_token < 0.39
    assert 0.14 < head / per_token < 0.15


def test_the_trainers_count_is_the_published_one():
    """788 M parameters: a dense convolution layer of 89.1 M, a sparse
    attention layer of 161.6 M, three sparse convolution layers of 167.9
    M, a quarter of the embedding and the final norm."""
    name, config = real_config()
    family = spec.load_family(config)
    ids = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: family.init_params(config, key, {"input_ids": ids}),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 16 * 3 * 2048 * 1536 + 2048 * 64 + 64
    norms = 2 * 2048
    assert count(shapes["layer_0"]) == conv + 3 * 2048 * 11776 + norms \
        == 89_139_200
    assert count(shapes["layer_1"]) == attention + experts + norms \
        == 161_616_064
    assert count(shapes["layer_2"]) == conv + experts + norms == 167_913_536
    assert count(shapes) == 89_139_200 + 161_616_064 + 3 * 167_913_536 \
        + 16384 * 2048 + 2048 == 788_052_352
    assert sorted(shapes["layer_1"]) == ["attention", "ffn_norm", "moe",
                                         "operator_norm"]
    assert shapes["layer_1"]["moe"]["router"].shape == (2048, 64)
    assert shapes["layer_1"]["moe"]["gate"].shape == (16, 2048, 1536)
    for leaf in config["check_leaves"]:
        reference.get_leaf(shapes, leaf)


def test_the_cut_is_what_the_files_say():
    """The configuration's ``reduced``, its published values, the
    layers held and the manifest agree."""
    name, config = real_config()
    entry = next(c for c in spec.load_manifest()["configs"]
                 if c["name"] == name)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    published = config["published"]
    assert published == {"num_hidden_layers": 40, "num_dense_layers": 2,
                         "num_experts": 64, "vocab_size": 65536}
    held = {key: config[key] for key in published}
    assert held == {"num_hidden_layers": 5, "num_dense_layers": 1,
                    "num_experts": 16, "vocab_size": 16384}
    # four chips share each layer: a quarter of the experts and of the rows
    assert held["num_experts"] * 4 == published["num_experts"]
    assert held["vocab_size"] * 4 == published["vocab_size"]
    assert "expert parallel over 4" in config["deployment"]
    assert (config["num_experts_per_tok"], config["first_expert"]) == (4, 0)
    # the published list whole; the leading dense layer and one period
    types = config["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10
    assert config["layers_held"] == [0, 2, 3, 4, 5]
    assert lfm2_reference.layer_kinds(config) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    period = types[2:6]
    assert types[2:] == period * 9 + period[:2]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips share a layer's experts: the parts of the sparse
    layer's output that the four shares give (experts 0 and 1, 2 and 3,
    ... of the tiny model's 8), added, are the uncut reference's layer
    output: every pair is computed by exactly one share."""
    from horovod_tpu.parallel import moe
    uncut = tiny_config(num_experts=8)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    hidden, width = uncut["hidden_size"], uncut["moe_intermediate_size"]
    stack = lambda key, a, b: jax.random.normal(key, (8, a, b)) / np.sqrt(a)
    p = {"router": jax.random.normal(keys[0], (hidden, 8)),
         "expert_bias": jnp.zeros(8),
         "gate": stack(keys[1], hidden, width),
         "up": stack(keys[2], hidden, width),
         "down": stack(keys[3], width, hidden)}
    x = jax.random.normal(keys[4], (2, 64, hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = lfm2_reference.sparse_ffn(x, p, uncut)
        parts, pairs = [], 0
        for first in (0, 2, 4, 6):
            of_share = lambda name: p[name][first:first + 2]
            y, routing = moe.routed_experts(
                x.reshape(-1, hidden), p["router"], p["expert_bias"],
                of_share("gate"), of_share("up"), of_share("down"),
                first_expert=first, top_k=2)
            parts.append(y.reshape(x.shape))
            pairs += int(moe.held_pairs(routing, first, 2)[0].group_sizes.sum())
            # and the share alone is the reference given the same share
            share = {**p, **{n: of_share(n) for n in ("gate", "up", "down")}}
            alone, _ = lfm2_reference.sparse_ffn(
                x, share, dict(uncut, first_expert=first))
            np.testing.assert_allclose(np.asarray(parts[-1]),
                                       np.asarray(alone), rtol=2e-4,
                                       atol=2e-5)
    assert pairs == 128 * 2
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_vocabulary_shares_add_up_to_the_uncut_model():
    """And its rows: the four models that hold a quarter of the
    embedding's rows each give, side by side, the logits of the
    reference that holds them all (each share on tokens of its own
    rows: a token whose row lies on another chip needs the exchange of
    a row-sharded embedding, which a one-chip cell leaves out)."""
    from horovod_tpu.models.lfm2 import LFM2LMHeadModel
    shares, held = 4, 512 // 4
    uncut, family, params, _ = _case("float32", vocab_size=512)
    cut = dict(uncut, vocab_size=held)
    model = LFM2LMHeadModel(family.program_config(cut))
    rows = params["word_embeddings"]["embedding"]
    rng = np.random.default_rng(0)
    got, want = [], []
    with jax.default_matmul_precision("highest"):
        for k in range(shares):
            ids = rng.integers(0, held, (2, 64), dtype=np.int32)
            share = dict(params, word_embeddings={
                "embedding": rows[k * held:(k + 1) * held]})
            got.append(model.apply({"params": share}, ids))
            whole = lfm2_reference.logits(
                params, {"input_ids": ids + k * held}, uncut)
            want.append(whole[..., k * held:(k + 1) * held])
            # and the share's loss is the reference's over the slice
            assert float(family.system_loss(cut)(
                share, {"input_ids": ids})) == pytest.approx(float(
                    lfm2_reference.loss(share, {"input_ids": ids}, cut)),
                    rel=2e-6)
    got, want = jnp.concatenate(got, -1), jnp.concatenate(want, -1)
    assert got.shape == want.shape == (2, 64, 512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def make_lfm2_root(root: str, **traffic) -> str:
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
    import horovod_tpu as hvd
    name = make_lfm2_root(str(tmp_path), batch_per_chip=2, seq_len=64)
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", str(2 ** 31 + 5), "--seconds", "0.5",
         "--trace", str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert run["window_compiles"] == 0
    # a dense convolution layer, a sparse attention and a sparse
    # convolution layer, a 512-row embedding, the norm
    moe = 4 * 3 * 64 * 32 + 64 * 8 + 8
    conv = 64 * 192 + 3 * 64 + 64 * 64
    assert run["n_params"] == (conv + 3 * 64 * 128 + 128) \
        + (2 * 64 * 64 + 2 * 64 * 32 + 2 * 16 + moe + 128) \
        + (conv + moe + 128) + 512 * 64 + 64
    # the check counted the batch's pairs on the experts held
    pairs = hvd.metrics_snapshot()["gauges"]["hvd_moe_pairs_held"]
    assert set(pairs) == {"layer=1", "layer=2"}
    assert all(0 < v < 128 * 2 for v in pairs.values())
    if not trace:
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0


def test_the_two_readers():
    readers = spec.metric_readers()
    ops = [["layer_*/mlp/gate [mxu]", 1.0],
           ["jit_step_fn/ragged-dot-none", 0.5],   # the compiler's kernel
           ["layer_*/moe/experts [convert]", 0.4],
           ["layer_*/moe/dispatch [loop fusion]", 0.3],
           ["rematted_computation/layer_*/moe/combine [loop fusion]", 0.1],
           ["layer_*/moe/router [mxu]", 0.1],
           ["layer_*/conv/in_proj [mxu]", 0.3],
           ["layer_*/attention/hvd_flash_fwd [custom-call]", 0.2]]
    share = readers["moe_share"]
    assert share.read({"trace": {"self_s": 4.0, "device_ops": ops}}) == \
        pytest.approx(100.0 * 1.4 / 4.0)
    assert share.read({"trace": {"self_s": 4.0, "device_ops": ops[:1]}}) == 0
    assert share.read({"trace": None}) is None and share.read({}) is None

    import horovod_tpu as hvd
    from horovod_tpu.common import metrics
    gib = readers["moe_dispatch_gib"]
    metrics.gauge("hvd_moe_dispatch_bytes").set(3 << 29)
    assert hvd.metrics_snapshot()["gauges"]["hvd_moe_dispatch_bytes"] == \
        3 << 29
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
