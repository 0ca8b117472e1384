"""The DeepSeek-V3 family in the benchmark: the comparison that decides
``correct`` and what it holds the routers' choices to, the FLOP counts
by hand, the parameter count, the cut against the files, the in-graph
trainer on a tiny cell, and the three readers it brings."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import _write, make_root, spec
from benchmarks.reference import common as reference
from benchmarks.reference import deepseek_v3 as deepseek_v3_reference

FAMILY = "deepseek_v3"
# One dense layer and two sparse; 4 heads of 24 = 16 + 8 for queries and
# keys and 16 for values, latent 32; 8 routed experts of which 4 are
# held, top 2, two shared experts' width; sequence 64.  The routed
# scale stays the published 2.448.
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
            qk_head_dim=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=4, num_experts_per_tok=2,
            vocab_size=512)


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
    published = dict(config["published"], n_routed_experts=8)
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
    choice; in float32 both choose alike, and no choice is a near-tie."""
    config, family, params, data = _case("float32", remat=remat)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(family.system_loss(config))(params, data)
        want = jax.jit(family.reference_loss(config))(params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    program = family.program_config(config)
    assert (program.n_routed_experts, program.experts_held) == (8, 4)
    assert (program.qk_nope_head_dim, program.shared_width) == (16, 64)
    assert program.routed_scaling_factor == 2.448 and program.remat is remat


@pytest.mark.parametrize("seed", [0, 5, 6])
def test_comparison_passes_bf16_and_fails_three_bits(seed):
    """As the other families' test.  bfloat16 flips a few near-ties of
    the routers' choices; the reference computes on the program's
    choice, and the three leaves read a few hundredths where weights
    that keep three bits of mantissa read several tenths."""
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


def test_a_choice_that_is_no_near_tie_fails_the_comparison(monkeypatch):
    """A program that routes elsewhere (here: the choice of another
    token) has no reference loss, whatever its own loss is."""
    config, family, params, data = _case("float32")
    from horovod_tpu.models import deepseek_v3
    honest = deepseek_v3.expert_choices
    assert np.isfinite(float(family.reference_loss(config)(params, data)))
    monkeypatch.setattr(
        deepseek_v3, "expert_choices",
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
    assert sorted(report) == [1, 2]
    assert bool(family.choices_agree(report)) is not rounded
    for r in report.values():
        assert int(r["choices"]) == 256 and r["pairs_expected"] == 128
        assert (int(r["differ"]) > 8) is rounded
    family.say(jax.device_get(report))
    import horovod_tpu as hvd
    assert set(hvd.metrics_snapshot()["gauges"]["hvd_moe_pairs_held"]) >= {
        "layer=1", "layer=2"}


def test_flops_by_hand_for_2_by_8192():
    name, config = real_config()
    family = spec.load_family(config)
    assert config["num_hidden_layers"] == 6
    projections = 2 * (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                       + 32 * 128 * 2048)
    # 4096 keys: half of 8192; 192 for q k^T and 128 for p v
    scores = 2 * 4096 * 32 * (192 + 128)
    dense = 3 * 2 * 2048 * 6144
    # the router over all 128; the shared expert's 1536 at every token;
    # 6 x 16 / 128 = 0.75 routed expert a token
    sparse = 2 * 2048 * 128 + 3 * 2 * 2048 * 1536 \
        + 0.75 * 3 * 2 * 2048 * 768
    head = 2 * 2048 * 16032
    per_token = 6 * (projections + scores) + dense + 5 * sparse + head
    assert family.attention_flops_per_token(config, 4096) == \
        projections + scores
    assert family.sparse_ffn_flops_per_token(config) == sparse
    assert family.flops_per_step(config, 2, 8192) == pytest.approx(
        3.0 * per_token * 2 * 8192, rel=1e-12)
    # 1093 MFLOP a token forward, 53.7 TFLOP a step; a sparse layer 163
    # MFLOP of which the scores are 84 and the projections 53: attention
    # 84 % of a sparse layer, three quarters of the step.
    assert 1092e6 < per_token < 1094e6
    assert 53.6e12 < family.flops_per_step(config, 2, 8192) < 53.8e12
    sparse_layer = projections + scores + sparse
    assert 162e6 < sparse_layer < 164e6
    assert 0.83 < (projections + scores) / sparse_layer < 0.85
    assert 0.74 < 6 * (projections + scores) / per_token < 0.76
    # the routed experts' products: 3.2 % of the step
    assert 0.03 < 5 * 0.75 * 3 * 2 * 2048 * 768 / per_token < 0.035


def test_the_flash_kernels_work_by_hand():
    """One call of each kernel at [2, 8192, 32, 192 | 128] in bfloat16:
    the causal half of 32 squares of 8192, 192 wide for scores and
    ``dS``'s products, 128 wide for ``P V``, ``dV`` and ``dP``."""
    name, config = real_config()
    work = spec.load_family(config).flash_kernel_work(config, 2, 8192)
    half = 2 * 2 * 32 * 8192 * 4096
    assert work["hvd_flash_fwd"][0] == half * (192 + 128)
    assert work["hvd_flash_bwd_dq"][0] == half * (192 + 128 + 192)
    assert work["hvd_flash_bwd_dkv"][0] == half * (192 + 128 + 128 + 192)
    rows = 2 * 8192 * 32
    q = k = rows * 192 * 2
    v = o = rows * 128 * 2
    stat = rows * 4
    assert work["hvd_flash_fwd"][1] == q + k + v + o + stat
    assert work["hvd_flash_bwd_dq"][1] == q + k + v + o + 2 * stat + q
    assert work["hvd_flash_bwd_dkv"][1] == q + k + v + o + 2 * stat + k + v
    # 1.37, 2.20 and 2.75 TFLOP a call: 7.0, 11.2 and 14.0 ms at the
    # peak, compute-bound by far (0.8 to 1.2 ms of traffic)
    flops = [work[n][0] for n in ("hvd_flash_fwd", "hvd_flash_bwd_dq",
                                  "hvd_flash_bwd_dkv")]
    assert [round(f / 1e12, 2) for f in flops] == [1.37, 2.2, 2.75]


def test_the_trainers_count_is_the_published_one():
    """687.5 M parameters: the dense layer 64.1 M (latent attention
    26.35 M and a SwiGLU of 37.75 M), five sparse layers of 111.55 M
    (attention, the router and its bias, the shared SwiGLU of 9.44 M, 16
    experts of 4.72 M), an eighth of the embedding and of the head."""
    name, config = real_config()
    family = spec.load_family(config)
    ids = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: family.init_params(config, key, {"input_ids": ids}),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    attention = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
                 + 32 * 128 * 2048)
    norms = 2 * 2048
    assert count(shapes["layer_0"]["attention"]) == attention == 26_345_984
    assert count(shapes["layer_0"]) == attention + 3 * 2048 * 6144 + norms \
        == 64_098_816
    sparse = (2048 * 128 + 128 + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768)
    assert count(shapes["layer_1"]["moe"]) == sparse == 85_196_928
    assert count(shapes["layer_1"]) == attention + sparse + norms \
        == 111_547_008
    assert count(shapes) == 64_098_816 + 5 * 111_547_008 \
        + 2 * 16032 * 2048 + 2048 == 687_502_976
    assert sorted(shapes["layer_1"]) == ["attention", "attention_norm",
                                         "ffn_norm", "moe"]
    assert sorted(shapes["layer_1"]["attention"]) == [
        "kv_down", "kv_norm", "kv_up", "out", "query"]
    assert shapes["layer_1"]["attention"]["kv_up"]["kernel"].shape == \
        (512, 32, 256)
    assert shapes["layer_1"]["moe"]["router"].shape == (2048, 128)
    assert shapes["layer_1"]["moe"]["gate"].shape == (16, 2048, 768)
    assert shapes["layer_1"]["moe"]["shared"]["gate"]["kernel"].shape == \
        (2048, 1536)
    assert shapes["lm_head"].shape == (16032, 2048)
    for leaf in config["check_leaves"]:
        reference.get_leaf(shapes, leaf)


def test_the_cut_is_what_the_files_say():
    """The configuration's ``reduced``, its published values, the
    deployment and the manifest agree; every other key is the
    catalog's."""
    name, config = real_config()
    manifest = spec.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    published = config["published"]
    assert published == {"num_hidden_layers": 48, "n_routed_experts": 128,
                         "vocab_size": 128256}
    held = {key: config[key] for key in published}
    assert held == {"num_hidden_layers": 6, "n_routed_experts": 16,
                    "vocab_size": 16032}
    # eight chips share each layer: an eighth of the experts and rows
    assert held["n_routed_experts"] * 8 == published["n_routed_experts"]
    assert held["vocab_size"] * 8 == published["vocab_size"]
    assert "expert parallel over 8" in config["deployment"]
    assert "eight pipeline stages of six layers" in config["deployment"]
    assert 8 * held["num_hidden_layers"] == published["num_hidden_layers"]
    as_published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "head_dim": 64, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "norm_topk_prob": True,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "rope_theta": 1000000, "rope_interleave": True,
        "rope_scaling": None, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "attention_bias": False,
        "hidden_act": "silu", "max_position_embeddings": 32768,
        "model_type": "deepseek_v3"}
    assert {key: config[key] for key in as_published} == as_published
    assert config["first_expert"] == 0
    # the cell: its mix is a quarter of the positions the config allows
    cell = next(w for w in manifest["workloads"] if w["config"] == name)
    traffic = spec.Cell(cell["name"]).traffic
    assert (traffic["batch_per_chip"], traffic["seq_len"]) == (2, 8192)
    assert 4 * traffic["seq_len"] == config["max_position_embeddings"]
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [cell["name"]]]
    assert new == ["latent_kv_share", "latent_expand_gib",
                   "sparse_ffn_share"]


def test_the_family_refuses_what_the_program_lacks():
    family = spec.load_family(tiny_config())
    with pytest.raises(ValueError, match="low-rank query"):
        family.program_config(tiny_config(q_lora_rank=16))
    with pytest.raises(ValueError, match="rotary scaling"):
        family.program_config(tiny_config(rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError, match="qk_head_dim"):
        family.program_config(tiny_config(qk_head_dim=32))
    with pytest.raises(ValueError, match="head of its own"):
        family.program_config(tiny_config(tie_word_embeddings=True))


def test_vocabulary_shares_add_up_to_the_uncut_model():
    """Its rows: the four models that hold a quarter of the embedding's
    and of the head's rows each give, side by side, the logits of the
    reference that holds them all (each share on tokens of its own
    rows)."""
    from horovod_tpu.models.deepseek_v3 import DeepseekV3LMHeadModel
    shares, held = 4, 512 // 4
    uncut, family, params, _ = _case("float32", vocab_size=512)
    cut = dict(uncut, vocab_size=held)
    model = DeepseekV3LMHeadModel(family.program_config(cut))
    rows, head = params["word_embeddings"]["embedding"], params["lm_head"]
    rng = np.random.default_rng(0)
    got, want = [], []
    with jax.default_matmul_precision("highest"):
        for k in range(shares):
            ids = rng.integers(0, held, (2, 64), dtype=np.int32)
            share = dict(params, lm_head=head[k * held:(k + 1) * held],
                         word_embeddings={
                             "embedding": rows[k * held:(k + 1) * held]})
            got.append(jax.jit(model.apply)({"params": share}, ids))
            whole = jax.jit(lambda p, b: deepseek_v3_reference.logits(
                p, b, uncut))(params, {"input_ids": ids + k * held})
            want.append(whole[..., k * held:(k + 1) * held])
    got, want = jnp.concatenate(got, -1), jnp.concatenate(want, -1)
    assert got.shape == want.shape == (2, 64, 512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


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
    name = make_family_root(str(tmp_path), batch_per_chip=2, seq_len=64)
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", str(2 ** 31 + 5), "--seconds", "0.5",
         "--trace", str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert run["window_compiles"] == 0
    attention = 64 * 4 * 24 + 64 * 40 + 32 + 32 * 4 * 32 + 4 * 16 * 64
    moe = 4 * 3 * 64 * 32 + 64 * 8 + 8 + 3 * 64 * 64
    assert run["n_params"] == 3 * (attention + 128) + 3 * 64 * 128 \
        + 2 * moe + 2 * 512 * 64 + 64
    # the check counted the batch's pairs on the experts held
    pairs = hvd.metrics_snapshot()["gauges"]["hvd_moe_pairs_held"]
    assert {"layer=1", "layer=2"} <= set(pairs)
    assert all(0 < pairs[k] < 128 * 2 for k in ("layer=1", "layer=2"))
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_mla_expand_bytes"] == 128 * 4 * (24 + 16) * 2
    if not trace:
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0


def test_the_three_readers():
    readers = spec.metric_readers()
    ops = [["layer_*/attention/hvd_flash_bwd_dkv [custom-call]", 1.0],
           ["jit_step_fn/ragged-dot-none", 0.5],   # the compiler's kernel
           ["layer_*/moe/shared/gate [mxu]", 0.4],
           ["layer_*/moe/dispatch [loop fusion]", 0.3],
           ["rematted_computation/layer_*/moe/combine [loop fusion]", 0.1],
           ["layer_*/attention/kv_up [mxu]", 0.2],
           ["rematted_computation/layer_*/attention/rotary [loop fusion]",
            0.15],
           ["layer_*/attention/kv_norm [loop fusion]", 0.05],
           ["layer_*/attention/query [mxu]", 0.3],
           ["layer_*/mlp/gate [mxu]", 0.2]]
    run = {"trace": {"self_s": 4.0, "device_ops": ops}}
    latent, sparse = readers["latent_kv_share"], readers["sparse_ffn_share"]
    assert latent.read(run) == pytest.approx(100.0 * 0.4 / 4.0)
    assert sparse.read(run) == pytest.approx(100.0 * 1.3 / 4.0)
    only_kernels = {"trace": {"self_s": 4.0, "device_ops": ops[:1]}}
    assert latent.read(only_kernels) == 0 and sparse.read(only_kernels) == 0
    for reader in (latent, sparse):
        assert reader.read({"trace": None}) is None and reader.read({}) is None
    # a path that merely ends in one of the names is no part of the latent
    assert not latent.is_latent_part("layer_*/mlp/kv_up [mxu]")
    assert latent.is_latent_part("layer_3/attention/kv_down/dot [mxu]")

    import horovod_tpu as hvd
    from horovod_tpu.common import metrics
    gib = readers["latent_expand_gib"]
    metrics.gauge("hvd_mla_expand_bytes").set(5 << 28)
    assert hvd.metrics_snapshot()["gauges"]["hvd_mla_expand_bytes"] == 5 << 28
    assert gib.read({"trace": {"self_s": 1.0}}) == 1.25
    assert gib.read({}) is None
    # A program that declares no such gauge (the parent commit): nothing.
    registry = metrics.MetricsRegistry()
    registry.gauge("hvd_other")
    was, metrics.REGISTRY = metrics.REGISTRY, registry
    try:
        assert gib.read({"trace": {"self_s": 1.0}}) is None
    finally:
        metrics.REGISTRY = was
