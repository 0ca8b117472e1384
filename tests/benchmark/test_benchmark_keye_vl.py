"""The Keye-VL family in the benchmark: the comparison that decides
``correct`` and what it holds the program's selection to, the FLOP
counts against the pairs at 16384, the parameter count, the cut against
the files and the catalog's row, the in-graph trainer on a tiny cell,
and the four readers it brings."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import _write, make_root, spec
from benchmarks.reference import common as reference

FAMILY = "keye_vl"
CELL = "keye-vl-2.0-30b-a3b_s16384_1chip"
# Two layers; 4 query heads over 2 key-value heads of 16 in sections of
# 2, 3 and 3 pairs; an indexer of 3 heads of 8 over one key head that
# keeps 24 keys a query; 8 routed experts of which 4 are held, top 2.
TINY = dict(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_experts_per_tok=2, vocab_size=512,
            rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                          "type": "default"},
            check_leaves=["layer_1/attention/indexer_query/kernel",
                          "layer_1/moe/router",
                          "layer_0/attention/query/kernel"])


def real_config():
    with open(os.path.join(spec.HERE, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def tiny_config(topk=24, **over):
    config = real_config()
    sa = dict(config["sa_config"], indexer_num_heads=3, indexer_head_dim=8,
              topk=topk)
    published = dict(config["published"], num_experts=8)
    return {**config, **TINY, "sa_config": sa, "published": published, **over}


def _case(dtype, seed=0, batch=2, seq=64, **over):
    config = tiny_config(compute_dtype=dtype, **over)
    family = spec.load_family(config)
    data = family.host_batch(config, batch, seq, np.random.default_rng(seed))
    params = family.init_params(config, jax.random.PRNGKey(seed), data)
    return config, family, params, data


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_family_losses_agree_in_float32(remat):
    """What the check compares, built from a configuration FILE's keys:
    the step's own loss against the reference's, both on the reference's
    own decisions, a sequence at a time; in float32 the program selects
    as the reference does."""
    config, family, params, data = _case("float32", remat=remat)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(family.system_loss(config))(params, data)
        want = jax.jit(family.reference_loss(config))(params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    program = family.program_config(config)
    assert (program.num_experts, program.experts_held) == (8, 4)
    assert (program.topk, program.indexer_num_heads,
            program.indexer_head_dim) == (24, 3, 8)
    assert program.mrope_section == (2, 3, 3) and program.remat is remat
    assert program.rope_theta == 1e7 and program.norm_topk_prob


@pytest.fixture(scope="module")
def bf16_check():
    """ONE program a side for the bfloat16 cases: the check's two sides
    and, third, the system with its weights rounded to three bits."""
    config, family, params, data = _case("bfloat16", remat=True, seq=256,
                                         topk=64)
    leaves = config["check_leaves"]
    round3 = lambda p: jax.tree.map(
        lambda a: a + jax.lax.stop_gradient(
            jax.lax.reduce_precision(a, 8, 3) - a), p)
    system, ref = family.system_loss(config), family.reference_loss(config)

    def rounded(p, batch):
        return system(round3(p), batch)
    return config, family, params, data, leaves, system, ref, rounded, round3


def test_comparison_passes_bf16_and_fails_three_bits(bf16_check):
    """The check's leaves read a few hundredths in bfloat16 where
    weights that keep three bits of mantissa read several tenths; an
    indexer's leaf, which only the alignment loss reaches, among them."""
    _, _, params, data, leaves, system, ref, rounded, _ = bf16_check
    ok, report = reference.compare(system, ref, params, data, leaves)
    assert ok, report
    assert set(report["grad_rel_l2"]) == set(leaves)
    assert any("indexer" in leaf for leaf in leaves)
    ok, report = reference.compare(rounded, ref, params, data, leaves)
    assert not ok, report


def test_the_selection_report_tells_a_rounded_program(bf16_check):
    """Measured against the reference's indexer: bfloat16 keeps nearly
    all of its pairs and strays by near-ties; three bits of mantissa
    keep visibly fewer and stray further."""
    config, family, params, data, *_, round3 = bf16_check
    ids = data["input_ids"][:1]
    report = jax.jit(lambda p, i: family.selection_report(config, p, i))
    fine = jax.device_get(report(params, ids))
    coarse = jax.device_get(jax.jit(
        lambda p, i: family.selection_report(config, p, i, round3(p)))(
            params, ids))
    for layer in fine:
        assert fine[layer]["agreement"] > coarse[layer]["agreement"]
        assert fine[layer]["agreement"] > 0.985
        assert coarse[layer]["agreement"] < 0.98
        assert int(coarse[layer]["stray"][2]) > int(fine[layer]["stray"][2])
    assert max(r["widest_gap"] for r in coarse.values()) \
        > max(r["widest_gap"] for r in fine.values())
    import horovod_tpu as hvd
    family.say(fine)
    gauges = hvd.metrics_snapshot()["gauges"]["hvd_dsa_selection_agreement"]
    assert gauges["layer=1"] == pytest.approx(
        float(fine[1]["agreement"]))


def _bits(keep):
    from horovod_tpu.ops import dsa
    return dsa.pack_mask(jnp.asarray(keep)[None])


@pytest.mark.parametrize("case,agree", [
    ("same", True), ("near-ties", True), ("a-stray-pair", True),
    ("not-near-ties", False), ("too-few", False)])
def test_selection_is_held_to_near_ties_and_a_share(case, agree):
    family = spec.load_family(tiny_config())
    assert 0.0 < family.NEAR_TIE < 0.5 and 0.8 < family.MIN_AGREEMENT < 1.0
    assert 0.0 < family.MAX_STRAY < 0.01
    rng = np.random.default_rng(0)
    theirs = np.tril(rng.random((256, 256)) < 0.5)
    near = theirs | np.tril(rng.random((256, 256)) < 0.1)
    mine = theirs.copy()
    allowed = int(family.MAX_STRAY * theirs.sum())
    assert allowed >= 2
    if case == "near-ties":
        extra = np.argwhere(near & ~theirs)[:50]
        mine[extra[:, 0], extra[:, 1]] = True
    if case in ("a-stray-pair", "not-near-ties"):
        far = np.argwhere(np.tril(~near))
        far = far[:allowed if case == "a-stray-pair" else allowed + 1]
        mine[far[:, 0], far[:, 1]] = True
    if case == "too-few":
        drop = np.argwhere(theirs)
        drop = drop[:int(len(drop) * (1.05 - family.MIN_AGREEMENT))]
        mine[drop[:, 0], drop[:, 1]] = False
    saw = {0: {"selected": _bits(theirs), "near": _bits(near)}}
    assert bool(family.selections_agree({0: _bits(mine)}, saw)) is agree


def test_a_program_that_selects_otherwise_has_no_loss_to_compare():
    """The check's system side: a program whose own selection is not the
    reference's but for near-ties has no loss (nan), so the comparison
    fails by its first limit whatever the leaves read."""
    config, family, params, data = _case("float32", seq=64)
    scrambled = lambda p: jax.tree.map(
        lambda a: a, reference.with_leaves(p, {
            "layer_0/attention/indexer_weights/kernel":
                -p["layer_0"]["attention"]["indexer_weights"]["kernel"]}))
    with jax.default_matmul_precision("highest"):
        fine = jax.jit(family.system_loss(config))(params, data)
        lost = jax.jit(family.system_loss(config, scrambled))(params, data)
    assert np.isfinite(float(fine)) and np.isnan(float(lost))


def test_flops_against_the_pairs_at_16384():
    from horovod_tpu.ops import dsa
    config = real_config()
    family = spec.load_family(config)
    assert dsa.selected_pairs(16384, 2048) == 31_458_304
    assert dsa.causal_pairs(16384) == 134_225_920
    assert family.pair_flops(config) == {"attention": 16384, "indexer": 2080}
    layer = 2 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 64 * 17
                 + 2048 * 16) + 2 * 2048 * 128 + 1 * 6 * 2048 * 768
    tokens = 16384 * (2 * 2048 * 18992 + 6 * layer)
    pairs = 6 * (16384 * 31_458_304 + 2080 * 134_225_920)
    assert family.flops_per_step(config, 1, 16384) == pytest.approx(
        3 * (tokens + pairs))
    assert family.flops_per_step(config, 1, 16384) == pytest.approx(
        33.5e12, rel=0.01)
    # 23.44 % of the triangle
    assert 31_458_304 / 134_225_920 == pytest.approx(0.2344, abs=1e-4)


def test_the_new_kernels_work_by_hand():
    config = real_config()
    family = spec.load_family(config)
    flash = family.flash_kernel_work(config, 1, 16384)
    product = 2 * 32 * 128 * 31_458_304
    wide, stat, bits = 16384 * 32 * 128 * 2, 16384 * 32 * 4, 16384 * 16384 // 8
    assert flash["hvd_flash_fwd_selected"] == (2 * product,
                                               4 * wide + stat + bits)
    assert flash["hvd_flash_bwd_selected"] == (5 * product,
                                               8 * wide + 2 * stat + bits)
    index = family.indexer_kernel_work(config, 1, 16384)
    of_index = 16384 * ((1024 + 64) * 2 + 16 * 4)
    assert index["hvd_dsa_select"] == (
        2080.0 * 134_225_920, of_index + bits + 16384 * 4)
    flops, moved = index["hvd_dsa_indexer_loss"]
    assert flops == 2 * 2080.0 * 134_225_920 + 2 * 32 * 128 * 31_458_304
    assert moved == 2 * of_index + bits + 16384 * (
        (32 + 4) * 128 * 2 + 32 * 4 + 8) + 32 * 16384 * 64 * 4


def test_the_cut_is_what_the_files_say():
    with open(os.path.join(os.path.dirname(spec.HERE), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config = real_config()
    # by name, not by place: a later PR appends after these
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/" \
           "config.json"
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 151936 // 8)
    # every other published number as published
    for key, value in dict(
            hidden_size=2048, head_dim=128, num_attention_heads=32,
            num_key_value_heads=4, moe_intermediate_size=768,
            intermediate_size=6144, num_experts_per_tok=8,
            num_local_experts=128, rope_theta=10000000, rms_norm_eps=1e-6,
            max_position_embeddings=262144, max_window_layers=48,
            decoder_sparse_step=1, norm_topk_prob=True).items():
        assert config[key] == value, key
    assert config["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="keye-vl-2.0-30b-a3b",
                        traffic="ingraph_1x16384", chips=1)
    new = ["dsa_select_share", "dsa_indexer_roofline",
           "flash_selected_roofline", "dsa_select_gib"]
    assert [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]] == new
    traffic = spec.Cell(CELL).traffic
    assert traffic["seq_len"] * config["num_experts_per_tok"] \
        // config["published"]["num_experts"] == 1024


def test_the_trainers_count_is_the_published_one():
    """A layer held: 96,899,456; six and the two vocabulary matrices:
    659,190,016 (ISSUE 49's count), from the program's own shapes."""
    config = real_config()
    family = spec.load_family(config)
    from horovod_tpu.models import keye_vl
    model = keye_vl.KeyeVLLMHeadModel(family.program_config(config))
    shapes = jax.eval_shape(
        lambda key, ids: model.init(key, ids)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    attention = 2 * 8_388_608 + 2 * 1_048_576 + 256 + 2_261_120
    assert count(shapes["layer_0"]["attention"]) == attention
    assert count(shapes["layer_0"]) == 96_899_456 \
        == attention + 262_144 + 4096 + 16 * 4_718_592
    assert count(shapes) == 659_190_016 == 6 * 96_899_456 + 77_791_232 + 2048


def test_the_family_refuses_what_the_program_lacks():
    family = spec.load_family(tiny_config())
    for over, match in [
            (dict(tie_word_embeddings=True), "head of its own"),
            (dict(sliding_window=4096), "no window"),
            (dict(mlp_only_layers=[0]), "every layer"),
            (dict(attention_bias=True), "no bias")]:
        with pytest.raises(ValueError, match=match):
            family.program_config(tiny_config(**over))
    sa = dict(tiny_config()["sa_config"], indexer_num_kv_heads=2)
    with pytest.raises(ValueError, match="one key head"):
        family.program_config(tiny_config(sa_config=sa))


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
    from horovod_tpu.ops import dsa
    from horovod_tpu.ops.pallas_attention import selected_tiles
    name = make_family_root(str(tmp_path), batch_per_chip=1, seq_len=64)
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", str(2 ** 31 + 5), "--seconds", "0.5",
         "--trace", str(trace), "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    result = json.loads((out / "result.json").read_text())
    assert result["correct"] is True and result["failed"] == 0
    assert run["window_compiles"] == 0
    attention = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 2 * 16 \
        + 64 * 3 * 8 + 64 * 8 + 2 * 8 + 64 * 3
    moe = 4 * 3 * 64 * 32 + 64 * 8
    assert run["n_params"] == 2 * (attention + moe + 2 * 64) \
        + 2 * 512 * 64 + 64
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_dsa_topk"] == 24
    assert gauges["hvd_dsa_indexer_heads"] == 3
    assert gauges["hvd_dsa_indexer_head_dim"] == 8
    assert gauges["hvd_dsa_pairs"] == {
        "which=selected": 24 * 25 // 2 + 40 * 24, "which=causal": 64 * 65 // 2}
    assert gauges["hvd_dsa_select_bytes"] == 64 // 32 * 64 * 4 + 64 * 4 \
        == dsa.select_bytes(1, 64)
    assert gauges["hvd_dsa_tiles"] == {
        "which=%s" % k: v for k, v in selected_tiles(64, 24).items()}
    assert gauges["hvd_rope_sections"] == {"stream=0": 2, "stream=1": 3,
                                           "stream=2": 3}
    assert gauges["hvd_moe_router"]["kind=softmax"] == 1
    # ``init`` measured the first batch's selection, a layer at a time
    agreement = gauges["hvd_dsa_selection_agreement"]
    assert all(0.9 < agreement["layer=%d" % i] <= 1.0 for i in (0, 1))
    if not trace:
        assert result["metrics"]["samples_per_s_chip"]["value"] > 0


def test_the_four_readers():
    readers = spec.metric_readers()
    ops = [["layer_*/attention/hvd_flash_bwd_selected [custom-call]", 0.9],
           ["layer_*/attention/hvd_flash_fwd_selected [custom-call]", 0.6],
           ["layer_*/attention/indexer_loss/hvd_dsa_indexer_loss "
            "[custom-call]", 0.5],
           ["layer_*/attention/select/hvd_dsa_select [custom-call]", 0.25],
           ["rematted_computation/layer_*/attention/indexer/indexer_query "
            "[mxu fusion]", 0.05],
           ["layer_*/attention/indexer_loss [loop fusion]", 0.1],
           ["layer_*/attention/query [mxu fusion]", 0.3],
           ["layer_*/moe/select [loop fusion]", 0.2],  # not attention's
           ["jit_step_fn/ragged-dot-none", 0.5]]
    run = {"trace": {"self_s": 6.0, "device_ops": ops}, "cell": CELL,
           "traced_steps": 5, "device": {"kind": "TPU v5 lite"}}
    share = readers["dsa_select_share"]
    assert share.read(run) == pytest.approx(100.0 * 0.9 / 6.0)
    assert not share.is_selection_part("layer_*/moe/select [loop fusion]")
    assert share.is_selection_part("layer_3/attention/indexer/x/mul [y]")
    config = spec.Cell(CELL).config
    family = spec.load_family(config)
    flash = family.flash_kernel_work(config, 1, 16384)
    index = family.indexer_kernel_work(config, 1, 16384)
    # six layers, five traced steps: 30 calls of each kernel
    selected = readers["flash_selected_roofline"]
    assert selected.read(run) == pytest.approx(
        100.0 * 30 * sum(w[0] for w in flash.values()) / (1.5 * 197e12))
    indexer = readers["dsa_indexer_roofline"]
    assert indexer.read(run) == pytest.approx(
        100.0 * 30 * sum(w[0] for w in index.values()) / (0.75 * 197e12))
    # one of the two among the ten groups: that kernel's own share
    alone = dict(run, trace={"self_s": 6.0, "device_ops": ops[:1]})
    assert selected.read(alone) == pytest.approx(
        100.0 * 30 * flash["hvd_flash_bwd_selected"][0] / (0.9 * 197e12))
    assert indexer.read(alone) == 0 and share.read(alone) == 0
    for reader in (share, selected, indexer, readers["dsa_select_gib"]):
        assert reader.read({"trace": None}) is None and reader.read({}) is None

    from horovod_tpu.common import metrics
    gib = readers["dsa_select_gib"]
    metrics.gauge("hvd_dsa_select_bytes").set(2.0 ** 25 + 65536)
    assert gib.read({"trace": {"self_s": 1.0}}) == pytest.approx(
        (2.0 ** 25 + 65536) / 2.0 ** 30)
    # what the selection writes for one layer at 16384: 0.031 GiB
    from horovod_tpu.ops import dsa
    assert dsa.select_bytes(1, 16384) / 2.0 ** 30 == pytest.approx(
        0.0313, abs=1e-4)
    # A program that declares no such gauge (the parent commit): nothing.
    registry = metrics.MetricsRegistry()
    registry.gauge("hvd_other")
    was, metrics.REGISTRY = metrics.REGISTRY, registry
    try:
        assert gib.read({"trace": {"self_s": 1.0}}) is None
    finally:
        metrics.REGISTRY = was


def test_the_references_membership_is_top_ks_with_its_ties():
    """``kept_by`` reconstructs from a ``top_k``'s values and indices the
    very set a scatter of the indices gives, tied rows among them (small
    whole numbers: most rows tie at their least value taken)."""
    from benchmarks.reference import keye_vl as reference
    scores = jax.random.randint(jax.random.PRNGKey(0), (64, 256), -3,
                                4).astype(jnp.float32)
    causal = jnp.arange(256)[None, :] <= (jnp.arange(64) * 4)[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    best, taken = jax.lax.top_k(masked, 40)
    scattered = jnp.zeros((64, 256), bool).at[
        jnp.arange(64)[:, None], taken].set(True)
    got = reference.kept_by(masked, best, taken)
    np.testing.assert_array_equal(np.asarray(got & causal),
                                  np.asarray(scattered & causal))
    tied = (masked == best[:, -1:]).sum(-1) > 1
    assert int(tied.sum()) > 32
