"""The AFMoE family in the benchmark: the comparison that decides
``correct`` and what it holds the routers' choices and the moved bias
to, the FLOP counts by hand, the parameter count, the cut against the
files and the catalog's row, the in-graph trainer on a tiny cell, and
the three readers it brings."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import _write, make_root, spec
from benchmarks.reference import afmoe as afmoe_reference
from benchmarks.reference import common as reference

FAMILY = "afmoe"
CELL = "trinity-mini_s16384_1chip"
# A dense window layer, then window, full, window, window with routed
# experts (the file's ``layers_held`` of its ``layer_types``); 4 query
# heads over 2 key-value heads of 16, a window of 16; 8 routed experts
# of which 4 are held, top 2, one shared expert; sequence 64.  The
# routed scale and the bias's coefficient stay the published ones.
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=16, num_experts=4, num_experts_per_tok=2,
            vocab_size=512)


@pytest.fixture(autouse=True)
def leave_the_layer_gauges_as_found():
    """A labelled gauge lives as long as its process, and another
    family's test reads the very set of layers its own cell counted:
    what a test here sets by layer goes with it."""
    from horovod_tpu.common import metrics
    names = ("hvd_moe_pairs_held", "hvd_moe_load_max_over_mean")
    before = {name: set(metrics.gauge(name).snapshot()) for name in names}
    yield
    for name in names:
        for key in set(metrics.gauge(name).snapshot()) - before[name]:
            metrics.gauge(name).drop(layer=key.split("=", 1)[1])


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


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_family_losses_agree_in_float32(remat):
    """What the check compares, built from a configuration FILE's keys:
    the step's own loss against the reference's, on the program's
    choice, a sequence at a time; in float32 both choose alike, no
    choice is a near-tie and the moved bias agrees."""
    config, family, params, data = _case("float32", remat=remat)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(family.system_loss(config))(params, data)
        want = jax.jit(family.reference_loss(config))(params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    program = family.program_config(config)
    assert (program.num_experts, program.experts_held) == (8, 4)
    assert program.layer_types == (
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention")
    assert program.ffn_types == ("dense",) + ("sparse",) * 4
    assert (program.sliding_window, program.shared_width) == (16, 32)
    assert program.route_scale == 2.826 and program.remat is remat
    assert program.load_balance_coeff == 0.001 and program.mup_enabled


@pytest.mark.parametrize("seed", [0, 5, 6])
def test_comparison_passes_bf16_and_fails_three_bits(seed):
    """As the other families' test: the check's leaves read a few
    hundredths in bfloat16 where weights that keep three bits of
    mantissa read several tenths."""
    # 256 tokens a sequence: the share of a layer's 512 choices that
    # may be another's is then more than a handful
    config, family, params, data = _case("bfloat16", seed=seed, remat=True,
                                         seq=256)
    leaves = config["check_leaves"]
    system, ref = family.system_loss(config), family.reference_loss(config)
    ok, report = reference.compare(system, ref, params, data, leaves)
    assert ok, report
    assert set(report["grad_rel_l2"]) == set(leaves)

    def rounded(p, batch):
        p = jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                jax.lax.reduce_precision(a, 8, 3) - a), p)
        return system(p, batch)
    ok, report = reference.compare(rounded, ref, params, data, leaves)
    assert not ok, report


def _report(differ, gap, choices=1000):
    return {1: {"choices": choices, "differ": jnp.int32(differ),
                "widest_gap": jnp.float32(gap)}}


@pytest.mark.parametrize("differ,gap,agree", [
    (0, 0.0, True), (1, 0.99, False), (999, 0.0, False)],
    ids=["same", "not-a-near-tie", "too-many"])
def test_choices_are_held_to_near_ties_and_a_share(differ, gap, agree):
    family = spec.load_family(tiny_config())
    assert 0.0 < family.NEAR_TIE < 0.1 and 0.9 < family.MIN_AGREEMENT < 1.0
    assert bool(family.choices_agree(_report(differ, gap))) is agree
    allowed = int((1.0 - family.MIN_AGREEMENT) * 1000)
    assert bool(family.choices_agree(_report(allowed, family.NEAR_TIE)))
    assert not bool(family.choices_agree(_report(allowed + 1, 0.0)))
    assert not bool(family.choices_agree(
        _report(1, family.NEAR_TIE * 1.01)))


@pytest.mark.parametrize("broken", ["another-choice", "another-rule"])
def test_a_program_that_routes_or_moves_otherwise_has_no_loss_to_compare(
        monkeypatch, broken):
    """A program that routes elsewhere (the choice of another token) has
    no loss on the system's side, whatever the step's loss is; one whose
    rule moves the bias otherwise (here: twice as far) than the
    reference's does on the same counts leaves the reference's side
    none."""
    config, family, params, data = _case("float32")
    system, ref = family.system_loss(config), family.reference_loss(config)
    assert np.isfinite(float(system(params, data)))
    assert np.isfinite(float(ref(params, data)))
    if broken == "another-choice":
        from horovod_tpu.models import afmoe
        honest = afmoe.expert_choices
        monkeypatch.setattr(
            afmoe, "expert_choices",
            lambda cfg, p, ids: {i: jnp.roll(c, 7, axis=0)
                                 for i, c in honest(cfg, p, ids).items()})
        assert np.isnan(float(family.system_loss(config)(params, data)))
    else:
        honest = family.move_selection_bias
        monkeypatch.setattr(
            family, "move_selection_bias",
            lambda params, chosen, step: honest(params, chosen, 2 * step))
        assert np.isnan(float(family.reference_loss(config)(params, data)))


def test_the_programs_own_choice_is_held_to_the_references(monkeypatch):
    """Both sides compute on the float32 reference's own choice
    (``reference_routing``: products at full precision, so that two
    compiled programs make ONE choice); the system side has no loss
    where the program's choice as it runs, in bfloat16, is another in
    more than a few near-ties."""
    config, family, params, data = _case("bfloat16")
    ids = data["input_ids"][:1]
    saw = jax.jit(lambda p, i: family.reference_routing(config, p, i))(
        params, ids)
    own = family.program_choice(config, params, ids)
    assert sorted(saw) == sorted(own) == [1, 2, 3, 4]
    assert saw[1]["own"].shape == own[1].shape == (64, 2)
    assert saw[1]["biased"].shape == (64, 8)
    # the reference's own choice lies no way under itself; another's may
    gap = afmoe_reference.gap_under_own
    assert float(gap(saw[1], saw[1]["own"]).max()) == 0.0
    assert float(gap(saw[1], (saw[1]["own"] + 1) % 8).max()) > 0.0
    report = jax.jit(lambda p, i: family.routing_report(
        config, *family.routing_of(config, p, i)))(params, ids)
    assert bool(family.choices_agree(report))
    assert np.isfinite(float(family.system_loss(config)(params, data)))
    honest = family.program_choice
    monkeypatch.setattr(
        family, "program_choice",
        lambda config, p, ids: {i: (c + 1) % 8
                                for i, c in honest(config, p, ids).items()})
    assert np.isnan(float(family.system_loss(config)(params, data)))


def test_the_moved_bias_is_held_to_a_rounding():
    """``biases_agree``: a bias one sign's share of the recentring away
    from the reference's (the least the rule moves anything) does not
    agree; the room is for the last bit alone."""
    config, family, params, data = _case("float32")
    chosen = family.program_choice(config, params, data["input_ids"][:1])
    assert bool(family.biases_agree(config, params, chosen))
    least = config["load_balance_coeff"] / 8
    assert family.BIAS_ATOL < least / 100
    honest = afmoe_reference.bias_after_update
    try:
        afmoe_reference.bias_after_update = \
            lambda bias, taken, cfg: honest(bias, taken, cfg).at[0].add(least)
        assert not bool(family.biases_agree(config, params, chosen))
    finally:
        afmoe_reference.bias_after_update = honest


@pytest.mark.parametrize("coeff,moves", [
    (1e-3, True), (0.0, False), (5e-4, False)],
    ids=["the-rule", "never-moved", "half-as-far"])
def test_the_timed_steps_own_program_is_held_to_the_rule(coeff, moves):
    """``step_moves_forced_bias``: the step the trainer times, called
    once under a bias that forces every token's choice, leaves the
    ``expert_bias`` leaves where the reference's rule puts them; a step
    whose program never moves the bias, or moves it half as far, does
    not (``ingraph`` then hands the trainer no loss)."""
    import dataclasses
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.training import make_afmoe_train_step
    config, family, _, data = _case("bfloat16", batch=1)
    assert list(family.forced_choice(config, 1)) == [1, 5]
    assert family.FORCED_BIAS_ATOL < config["load_balance_coeff"] / 8 / 10
    program = dataclasses.replace(family.program_config(config),
                                  load_balance_coeff=coeff)
    init_fn, step_fn, sharding = make_afmoe_train_step(
        program, build_mesh({"dp": 1}, jax.devices()[:1]))
    ids = jax.device_put(data["input_ids"], sharding)
    state = init_fn(jax.random.PRNGKey(0), ids)
    assert family.step_moves_forced_bias(config, step_fn, state, ids) is moves


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
    whose weights keep three bits of mantissa chooses otherwise far
    more often, and not by near-ties."""
    config, family, params, data = _case("bfloat16")
    program = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 8, 3), params) \
        if rounded else None
    report = jax.jit(lambda p, q, ids: family.routing_report(
        config, *family.routing_of(config, p, ids, q)))(
            params, params if program is None else program,
            data["input_ids"])
    assert sorted(report) == [1, 2, 3, 4]
    assert bool(family.choices_agree(report)) is not rounded
    for r in report.values():
        assert int(r["choices"]) == 256 and r["pairs_expected"] == 128
        assert float(r["load_max_over_mean"]) >= 1.0
    assert (sum(int(r["differ"]) for r in report.values()) > 32) is rounded
    family.say(jax.device_get(report))
    import horovod_tpu as hvd
    gauges = hvd.metrics_snapshot()["gauges"]
    for name in ("hvd_moe_pairs_held", "hvd_moe_load_max_over_mean"):
        assert set(gauges[name]) >= {"layer=%d" % i for i in (1, 2, 3, 4)}


def test_flops_by_hand_for_1_by_16384():
    """ISSUE 45's reckoning: 813.4 MFLOP a token forward, 39.98 TFLOP a
    step; every layer at half the square would be 1224.5."""
    name, config = real_config()
    family = spec.load_family(config)
    assert config["num_hidden_layers"] == 5
    seq = 16384
    projections = 2 * (3 * 2048 * 32 * 128 + 2048 * 4 * 256)
    band = 2048 * 2049 / 2 + (seq - 2048) * 2048     # pairs a head
    window = 2 * band / seq * 32 * (128 + 128)
    full = 2 * (seq / 2) * 32 * (128 + 128)
    dense = 3 * 2 * 2048 * 6144
    # the router over all 128; the shared expert's 1024 at every token;
    # 8 x 16 / 128 = one routed expert a token
    sparse = 2 * 2048 * 128 + (1 + 1.0) * 3 * 2 * 2048 * 1024
    head = 2 * 2048 * 25024
    per_token = 5 * projections + 4 * window + full + dense + 4 * sparse \
        + head
    assert family.attention_flops_per_token(
        config, "sliding_attention", seq) == projections + window
    assert family.attention_flops_per_token(
        config, "full_attention", seq) == projections + full
    assert family.sparse_ffn_flops_per_token(config) == sparse
    assert family.flops_per_token(config, seq) == pytest.approx(per_token,
                                                                rel=1e-12)
    assert family.flops_per_step(config, 1, seq) == pytest.approx(
        3.0 * per_token * seq, rel=1e-12)
    # to four figures, part by part (MFLOP a token)
    parts = [5 * projections, 4 * window, full, 4 * sparse, dense, head]
    assert [round(p / 1e6, 1) for p in parts] == [
        272.6, 125.8, 134.2, 102.8, 75.5, 102.5]
    assert round(per_token / 1e6, 1) == 813.4
    assert round(3 * per_token * seq / 1e12, 2) == 39.98
    assert round((per_token - 4 * window + 4 * full) / 1e6, 1) == 1224.5
    # the band is under a quarter of the triangle; attention's modules
    # (projections and scores) are 65 % of the step
    assert 0.234 < band / (seq * seq / 2) < 0.235
    attention = 5 * projections + 4 * window + full
    assert 0.65 < attention / per_token < 0.66
    assert 0.06 < 4 * 3 * 2 * 2048 * 1024 / per_token < 0.065


def test_the_flash_kernels_work_by_hand():
    """One call of each of the four kernels at [1, 16384, 32, 128] in
    bfloat16: the full layer's at the causal half of 32 squares, the
    window layers' at 32 bands; two products forward, five backward."""
    name, config = real_config()
    work = spec.load_family(config).flash_kernel_work(config, 1, 16384)
    assert sorted(work) == ["hvd_flash_bwd", "hvd_flash_bwd_window",
                            "hvd_flash_fwd", "hvd_flash_fwd_window"]
    half = 2 * 32 * 16384 * 8192 * 128
    band = 2 * 32 * (2048 * 2049 / 2 + 14336 * 2048) * 128
    assert work["hvd_flash_fwd"][0] == 2 * half
    assert work["hvd_flash_bwd"][0] == 5 * half
    assert work["hvd_flash_fwd_window"][0] == 2 * band
    assert work["hvd_flash_bwd_window"][0] == 5 * band
    rows = 16384 * 32
    wide, stat = rows * 128 * 2, rows * 4
    for suffix in ("", "_window"):
        assert work["hvd_flash_fwd" + suffix][1] == 4 * wide + stat
        assert work["hvd_flash_bwd" + suffix][1] == 8 * wide + 2 * stat
    # 2.20 and 5.50 TFLOP a call on the triangle (11.2 and 27.9 ms at
    # the peak), 0.52 and 1.29 on the band (2.6 and 6.5 ms)
    assert [round(work[k][0] / 1e12, 2) for k in sorted(work)] == [
        5.5, 1.29, 2.2, 0.52]


def test_the_trainers_count_is_the_published_one():
    """705.47 M parameters: the dense layer 65.02 M (attention 27.26 M
    and a SwiGLU of 37.75 M), four sparse layers of 134.49 M (attention,
    the router and its bias, the shared SwiGLU of 6.29 M, 16 experts of
    6.29 M), an eighth of the embedding and of the head."""
    name, config = real_config()
    family = spec.load_family(config)
    ids = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: family.init_params(config, key, {"input_ids": ids}),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    attention = 3 * 2048 * 32 * 128 + 2048 * 4 * 256 + 2 * 128
    norms = 4 * 2048
    assert count(shapes["layer_0"]["attention"]) == attention == 27_263_232
    assert count(shapes["layer_0"]) == attention + 3 * 2048 * 6144 + norms \
        == 65_020_160
    sparse = 2048 * 128 + 128 + 3 * 2048 * 1024 + 16 * 3 * 2048 * 1024
    assert count(shapes["layer_1"]["moe"]) == sparse == 107_217_024
    assert count(shapes["layer_1"]) == attention + sparse + norms \
        == 134_488_448
    assert count(shapes) == 65_020_160 + 4 * 134_488_448 \
        + 2 * 25024 * 2048 + 2048 == 705_474_304
    assert sorted(shapes["layer_1"]) == [
        "attention", "input_norm", "moe", "post_attention_norm",
        "post_mlp_norm", "pre_mlp_norm"]
    assert sorted(shapes["layer_1"]["attention"]) == [
        "gate", "key_norm", "key_value", "out", "query", "query_norm"]
    assert shapes["layer_1"]["attention"]["gate"]["kernel"].shape == \
        (2048, 32, 128)
    assert shapes["layer_1"]["attention"]["key_value"]["kernel"].shape == \
        (2048, 4, 256)
    assert shapes["layer_1"]["moe"]["router"].shape == (2048, 128)
    assert shapes["layer_1"]["moe"]["expert_bias"].shape == (128,)
    assert shapes["layer_1"]["moe"]["gate"].shape == (16, 2048, 1024)
    assert shapes["lm_head"].shape == (25024, 2048)
    for leaf in config["check_leaves"]:
        reference.get_leaf(shapes, leaf)
    # a window layer's gate and its router (the first sparse layer's, as
    # the other sparse cells'), and the full layer's query
    kinds = afmoe_reference.layer_kinds(config)
    assert [kind for kind, _ in kinds] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert [dense for _, dense in kinds] == [True] + [False] * 4
    assert config["check_leaves"] == [
        "layer_1/attention/gate/kernel", "layer_4/moe/router",
        "layer_2/attention/query/kernel"]


def test_the_cut_is_what_the_files_say():
    """The configuration's ``reduced``, its published values, the
    deployment and the manifest agree; every other key is the catalog's
    row, where the catalog is at hand."""
    name, config = real_config()
    manifest = spec.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    assert name == "trinity-mini" and entry == manifest["configs"][-1]
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    published = config["published"]
    assert published == {"num_hidden_layers": 32, "num_dense_layers": 2,
                         "num_experts": 128, "vocab_size": 200192}
    held = {key: config[key] for key in published}
    assert held == {"num_hidden_layers": 5, "num_dense_layers": 1,
                    "num_experts": 16, "vocab_size": 25024}
    # eight chips share each layer: an eighth of the experts and rows
    assert held["num_experts"] * 8 == published["num_experts"]
    assert held["vocab_size"] * 8 == published["vocab_size"]
    assert "expert parallel over 8" in config["deployment"]
    assert "eight pipeline stages of four layers" in config["deployment"]
    assert config["layers_held"] == [0, 2, 3, 4, 5]
    assert len(config["layer_types"]) == 32      # kept whole
    assert config["first_expert"] == 0
    for key in ("source", "published", "reduced", "deployment", "assumed",
                "departures"):
        assert config[key], key
    as_published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
        "rope_theta": 10000, "rope_scaling": None,
        "global_attn_every_n_layers": 4, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
        "route_scale": 2.826, "load_balance_coeff": 0.001,
        "mup_enabled": True, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
        "num_expert_groups": 1, "num_limited_groups": 1,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "hidden_act": "silu", "use_grouped_mm": True}
    assert {key: config[key] for key in as_published} == as_published
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["source_url"] == config["source"]
        differ = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differ == set(config["reduced"])
    # the cell: one chip, one sequence an eighth of the positions the
    # config allows; the manifest gains it and its three metrics last
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, name, "ingraph_1x16384", 1)
    traffic = spec.Cell(cell["name"]).traffic
    assert (traffic["batch_per_chip"], traffic["seq_len"]) == (1, 16384)
    assert 8 * traffic["seq_len"] == config["max_position_embeddings"]
    assert (traffic["pool_batches"], traffic["fetch_every"],
            traffic["warmup_steps"], traffic["traced_steps"]) == (8, 10, 3, 5)
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [cell["name"]]]
    assert new == ["flash_window_roofline", "window_tile_fill",
                   "gated_attention_share"]
    assert [m["name"] for m in manifest["per_layer"][-3:]] == new
    # 1024 rows an expert: the tokens' 8 choices over 128 experts
    assert traffic["seq_len"] * config["num_experts_per_tok"] \
        // published["num_experts"] == 1024


def test_the_family_refuses_what_the_program_lacks():
    family = spec.load_family(tiny_config())
    with pytest.raises(ValueError, match="rotary scaling"):
        family.program_config(tiny_config(rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError, match="head of its own"):
        family.program_config(tiny_config(tie_word_embeddings=True))


def make_family_root(root: str, **traffic) -> str:
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
    name = make_family_root(str(tmp_path), batch_per_chip=1, seq_len=64)
    out = tmp_path / "out"
    out.mkdir()
    before = hvd.metrics_snapshot()["counters"].get(
        "hvd_moe_bias_updates_total", 0)
    run = ingraph.main(
        ["--workload", name, "--seed", str(2 ** 31 + 5), "--seconds", "0.5",
         "--trace", str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert run["window_compiles"] == 0
    attention = 3 * 64 * 4 * 16 + 64 * 2 * 32 + 2 * 16
    moe = 4 * 3 * 64 * 32 + 64 * 8 + 8 + 3 * 64 * 32
    assert run["n_params"] == 5 * (attention + 4 * 64) + 3 * 64 * 128 \
        + 4 * moe + 2 * 512 * 64 + 64
    gauges = hvd.metrics_snapshot()["gauges"]
    # the check counted the batch's pairs on the experts held
    pairs = gauges["hvd_moe_pairs_held"]
    assert all(0 < pairs["layer=%d" % i] < 64 * 2 for i in (1, 2, 3, 4))
    assert all(gauges["hvd_moe_load_max_over_mean"]["layer=%d" % i] >= 1
               for i in (1, 2, 3, 4))
    assert gauges["hvd_attention_window"] == 16
    assert gauges["hvd_moe_bias_step"] == 0.001
    # every step of the run moved the bias, and before them the one
    # that ``init`` held to the rule
    assert hvd.metrics_snapshot()["counters"][
        "hvd_moe_bias_updates_total"] == before + run["attempted"] + 1
    from horovod_tpu.ops.pallas_attention import band_tiles
    assert gauges["hvd_flash_window_fill"] == band_tiles(64, 16)["fill"]
    if not trace:
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0


def test_the_three_readers():
    readers = spec.metric_readers()
    ops = [["layer_*/attention/hvd_flash_bwd_window [custom-call]", 0.2],
           ["layer_*/attention/hvd_flash_fwd_window [custom-call]", 0.1],
           ["layer_*/attention/hvd_flash_bwd [custom-call]", 0.14],
           ["layer_*/attention/gate [mxu fusion]", 0.3],
           ["rematted_computation/layer_*/attention/rotary [loop fusion]",
            0.15],
           ["layer_*/attention/qk_norm/query_norm [loop fusion]", 0.05],
           ["layer_*/attention/query [mxu fusion]", 0.3],
           ["layer_*/moe/gate [mxu fusion]", 0.2],   # the experts', not a head's
           ["layer_*/mlp/gate [mxu fusion]", 0.2],
           ["jit_step_fn/ragged-dot-none", 0.5]]
    run = {"trace": {"self_s": 4.0, "device_ops": ops}, "cell": CELL,
           "traced_steps": 5, "device": {"kind": "TPU v5 lite"}}
    gated = readers["gated_attention_share"]
    assert gated.read(run) == pytest.approx(100.0 * 0.5 / 4.0)
    assert not gated.is_gated_part("layer_*/moe/gate [mxu fusion]")
    assert gated.is_gated_part("layer_3/attention/qk_norm/key_norm/mul [x]")
    roofline = readers["flash_window_roofline"]
    family = spec.load_family(spec.Cell(CELL).config)
    work = family.flash_kernel_work(spec.Cell(CELL).config, 1, 16384)
    # four window layers, five traced steps: 20 calls of each kernel
    flops = 20 * (work["hvd_flash_fwd_window"][0]
                  + work["hvd_flash_bwd_window"][0])
    assert roofline.read(run) == pytest.approx(
        100.0 * flops / (0.3 * 197e12))
    assert roofline.share(run, roofline.FULL_KERNELS, roofline.FULL_KIND)[0] \
        == pytest.approx(100.0 * 5 * work["hvd_flash_bwd"][0]
                         / (0.14 * 197e12))
    # one of the two among the ten groups: that kernel's own share
    alone = dict(run, trace={"self_s": 4.0, "device_ops": ops[:1]})
    assert roofline.read(alone) == pytest.approx(
        100.0 * 20 * work["hvd_flash_bwd_window"][0] / (0.2 * 197e12))
    only_others = dict(run, trace={"self_s": 4.0, "device_ops": ops[3:4]})
    assert roofline.read(only_others) == 0
    assert gated.read(dict(run, trace={"self_s": 4.0,
                                       "device_ops": ops[:3]})) == 0
    for reader in (gated, roofline, readers["window_tile_fill"]):
        assert reader.read({"trace": None}) is None and reader.read({}) is None

    import horovod_tpu as hvd
    from horovod_tpu.common import metrics
    fill = readers["window_tile_fill"]
    metrics.gauge("hvd_flash_window_fill").set(0.8)
    assert hvd.metrics_snapshot()["gauges"]["hvd_flash_window_fill"] == 0.8
    assert fill.read({"trace": {"self_s": 1.0}}) == pytest.approx(80.0)
    # A program that declares no such gauge (the parent commit): nothing.
    registry = metrics.MetricsRegistry()
    registry.gauge("hvd_other")
    was, metrics.REGISTRY = metrics.REGISTRY, registry
    try:
        assert fill.read({"trace": {"self_s": 1.0}}) is None
    finally:
        metrics.REGISTRY = was
