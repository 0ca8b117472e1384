"""The one seam of the causal-LM families (``training.CausalLMFamily``,
``models/layers.py``): every row of the table goes through the same
cases, so a sixth family is tested by being a row.  What is a family's
own (its layers, its bytes by hand, its gauges) is in its own file.
"""

import ast
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.models import (afmoe, deepseek_v3, gpt, granite, keye_vl,
                                layers, lfm2, qwen3_next)
from horovod_tpu.parallel.mesh import build_mesh

# A v5e's ``memory_stats()["bytes_limit"]``.
V5E = 16_911_433_728
# A row, its public builder, its tiny configuration, and the benchmark's
# cell as the rule sees it: the published widths (the cell's cut of the
# layers and the experts held), the fp32 parameters and the optimizer's
# two moments in bytes, and ``(sequences, seq, memory, names kept)``.
FAMILIES = {
    "gpt": dict(
        row=training._gpt_family, make=training.make_gpt_train_step,
        tiny=gpt.gpt_tiny_config, cell=gpt.gpt2_medium_config(),
        state=3 * 4 * 354_823_168, kept=[
            (16, 1024, V5E, gpt.REMAT_NAMES),
            (16, 1024, V5E // 16, gpt.FLASH_NAMES),
            (16, 1024, None, gpt.REMAT_NAMES),
            # twice the batch no longer fits beside the state and the
            # margin, and GPT gives up every matmul's output at once
            (32, 1024, V5E, gpt.FLASH_NAMES)]),
    "granite": dict(
        row=training._granite_family, make=training.make_granite_train_step,
        tiny=granite.granite_tiny_config,
        cell=granite.GraniteConfig(vocab_size=12544),
        state=12 * 772_160_448, kept=[
            (2, 4096, None, granite.REMAT_NAMES),
            # gate and up (2.68 GB) fit, the input projection's output
            # (1.26 GB) beside them does not
            (2, 4096, V5E, granite.FLASH_NAMES + ("gate_up",)),
            (2, 4096, 2 * V5E, granite.REMAT_NAMES),
            (8, 4096, V5E, granite.FLASH_NAMES),
            (2, 4096, 1 << 20, granite.FLASH_NAMES)]),
    "lfm2": dict(
        row=training._lfm2_family, make=training.make_lfm2_train_step,
        tiny=lfm2.lfm2_tiny_config,
        cell=lfm2.LFM2Config(
            vocab_size=16384,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            ffn_types=("dense",) + ("sparse",) * 4, experts_held=16),
        state=12 * 788_052_352, kept=[
            (2, 4096, None, lfm2.REMAT_NAMES),
            (2, 4096, V5E, lfm2.REMAT_NAMES),   # 2.17 GB beside 9.46 + 4.2
            (4, 4096, V5E, lfm2.REMAT_NAMES[:-2]),
            (2, 4096, 1 << 20, lfm2.KEPT_NAMES)]),
    "deepseek_v3": dict(
        row=training._deepseek_v3_family,
        make=training.make_deepseek_v3_train_step,
        tiny=deepseek_v3.deepseek_v3_tiny_config,
        cell=deepseek_v3.DeepseekV3Config(
            vocab_size=16032, num_hidden_layers=6, experts_held=16),
        state=8_250_000_000, kept=[
            # the expanded keys and values (2.0 GB over six layers) fit,
            # the routed experts' gate and up (1.5 GB) do not
            (2, 8192, V5E, deepseek_v3.KEPT_NAMES + (
                "gate_up", deepseek_v3.EXPANDED_KV_NAME)),
            (2, 8192, None, deepseek_v3.REMAT_NAMES),
            (2, 8192, 12_000_000_000, deepseek_v3.KEPT_NAMES)]),
    "qwen3_next": dict(
        row=training._qwen3_next_family,
        make=training.make_qwen3_next_train_step,
        tiny=qwen3_next.qwen3_next_tiny_config,
        cell=qwen3_next.Qwen3NextConfig(
            vocab_size=18992, num_hidden_layers=4, experts_held=32),
        state=7_508_000_000, kept=[
            (1, 8192, V5E, qwen3_next.REMAT_NAMES),
            (1, 8192, None, qwen3_next.REMAT_NAMES),
            (1, 8192, 10_000_000_000, qwen3_next.KEPT_NAMES),
            # at two sequences the routed experts' buffers no longer fit
            (2, 8192, V5E, qwen3_next.REMAT_NAMES[:-2])]),
    "afmoe": dict(
        row=training._afmoe_family, make=training.make_afmoe_train_step,
        tiny=afmoe.afmoe_tiny_config,
        cell=afmoe.AfmoeConfig(
            vocab_size=25024, num_hidden_layers=5, num_dense_layers=1,
            experts_held=16, layer_types=(
                afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL, afmoe.SLIDING,
                afmoe.SLIDING)),
        state=12 * 705_474_304, kept=[
            # attention's three input projections (1.51 GB over five
            # layers) fit beside gate and up, the routed experts' gate
            # and up (2.15 GB over a buffer an eighth full) do not
            (1, 16384, V5E, afmoe.KEPT_NAMES + (
                "gate_up", afmoe.ATTENTION_IN_NAME)),
            (1, 16384, None, afmoe.REMAT_NAMES),
            (2, 16384, V5E, afmoe.KEPT_NAMES + ("gate_up",)),
            (1, 16384, 12_000_000_000, afmoe.KEPT_NAMES)]),
    "keye_vl": dict(
        row=training._keye_vl_family, make=training.make_keye_vl_train_step,
        tiny=keye_vl.keye_vl_tiny_config,
        cell=keye_vl.KeyeVLConfig(
            vocab_size=18992, num_hidden_layers=6, experts_held=16),
        state=12 * 659_190_016, kept=[
            # attention's projections (1.0 GB) and the routed experts'
            # gate and up (2.4 GB) fit, the sorted rows (3.2 GB) do not
            (1, 16384, V5E, keye_vl.KEPT_NAMES + (
                keye_vl.ATTENTION_IN_NAME, "moe_gate_up")),
            (2, 16384, V5E, keye_vl.KEPT_NAMES + (
                keye_vl.ATTENTION_IN_NAME,)),
            (1, 16384, None, keye_vl.REMAT_NAMES),
            (1, 16384, 11_000_000_000, keye_vl.KEPT_NAMES)]),
}
every_family = pytest.mark.parametrize("family", list(FAMILIES))


def test_every_row_of_the_table_is_a_case_here():
    rows = {name for name, value in vars(training).items()
            if name.endswith("_family") and callable(value)}
    assert rows == {"_%s_family" % name for name in FAMILIES}
    for name, case in FAMILIES.items():
        assert case["row"]().label == name


@pytest.mark.parametrize("limit,sizes,want", [
    (None, (9, 5, 1), "abc"),      # no limit reported: the most
    (100, (9, 5, 1), "abc"),       # 9 + 40 + 25 <= 100
    (64, (9, 5, 1), "ab"),         # 9 + 40 + 16 > 64, 5 + 56 fits
    (60, (5, 9, 1), "abc"),        # 5 + 40 + 15 == 60: at the line
    (59, (6, 9, 1), "a"),          # 6 + 54 and 9 + 54 > 59
    (40, (9, 5, 1), "a"),          # nothing fits: what is always kept
], ids=["no-limit", "all", "second", "at-the-line", "past-the-line",
        "nothing-fits"])
def test_the_rule_takes_the_first_candidate_that_fits(limit, sizes, want):
    """``kept_across_remat`` on integers: beside a state of 40 and a
    quarter of the memory."""
    candidates = layers.prefixes(("a", "b", "c"), 1)
    assert candidates == (("a", "b", "c"), ("a", "b"), ("a",))
    bytes_of = dict(zip(candidates, sizes)).__getitem__
    assert layers.kept_across_remat(candidates, bytes_of, 40, limit) == \
        tuple(want)


@every_family
def test_remat_keeps_what_fits_the_device(family):
    """The rule on the cell's integers, as the builder asks it: the
    row's candidates in their order, the row's bytes for the batch on
    one device, beside the state and a quarter of the memory."""
    case = FAMILIES[family]
    row = case["row"]()
    assert row.remat_candidates[0] == row.model.remat_names  # the default
    for sequences, seq, limit, want in case["kept"]:
        kept_bytes = functools.partial(
            row.remat_bytes, sequences=sequences, seq=seq,
            config=case["cell"])
        got = layers.kept_across_remat(row.remat_candidates, kept_bytes,
                                       case["state"], limit)
        assert got == want, (sequences, seq, limit)
        assert got in row.remat_candidates
        if limit is not None and got != row.remat_candidates[-1]:
            assert kept_bytes(got) + case["state"] + limit // 4 <= limit


def _tiny_step(family, axes, **config):
    case = FAMILIES[family]
    cfg = case["tiny"](**config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    built = case["make"](cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2 * chips, 64), 0,
                             cfg.vocab_size)
    return cfg, built, ids


def _kept(family):
    gauge = hvd.metrics_snapshot()["gauges"]["hvd_remat_kept_bytes"]
    prefix = "family=%s,names=" % family
    return {key[len(prefix):]: value for key, value in gauge.items()
            if key.startswith(prefix)}


@every_family
def test_the_step_decides_by_its_device_memory(family, monkeypatch):
    """The builder hands the rule what it sees when the step is traced,
    and the kept bytes show in the snapshot under the row's label: the
    CPU reports no memory, so every name stays; on a device that
    reports little the matmuls whose outputs went are traced a second
    time and what is always kept stays."""
    row = FAMILIES[family]["row"]()
    cfg, (init_fn, step_fn, sharding), ids = _tiny_step(
        family, {"dp": 1}, remat=True)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    products = lambda text: (text.count("dot_general")
                             + text.count("ragged_dot"))
    text = str(jax.make_jaxpr(step_fn)(*state, ids))
    most, least = row.remat_candidates[0], row.remat_candidates[-1]
    assert _kept(family)["+".join(most)] == row.remat_bytes(
        most, *ids.shape, cfg)

    monkeypatch.setattr("horovod_tpu.training._memory_limit",
                        lambda device: 1 << 20)
    small_step = _tiny_step(family, {"dp": 1}, remat=True)[1][1]
    small_text = str(jax.make_jaxpr(small_step)(*state, ids))
    assert _kept(family)["+".join(least)] == row.remat_bytes(
        least, *ids.shape, cfg)
    assert products(small_text) > products(text)


@every_family
def test_every_row_honours_the_contract(family):
    """``(init_fn, step_fn, batch_sharding)`` on the 8-device mesh: the
    batch over ``dp``, a state born sharded, a step that returns the
    state's like and a finite loss, and the kept bytes of ONE device's
    share of the batch on record under the row's label."""
    row = FAMILIES[family]["row"]()
    cfg, (init_fn, step_fn, sharding), ids = _tiny_step(
        family, {"dp": 4, "tp": 2}, remat=True, dtype=jnp.float32)
    assert sharding.spec == jax.sharding.PartitionSpec("dp", None)
    ids = jax.device_put(ids, sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    assert {len(leaf.sharding.device_set)
            for leaf in jax.tree.leaves(params)} == {8}
    new_params, new_opt_state, loss = step_fn(params, opt_state, ids)
    assert np.isfinite(float(loss))
    assert jax.tree.structure(new_params) == jax.tree.structure(params)
    assert jax.tree.structure(new_opt_state) == \
        jax.tree.structure(opt_state)
    names = row.remat_candidates[0]
    assert _kept(family)["+".join(names)] == row.remat_bytes(
        names, ids.shape[0] // 4, ids.shape[1], cfg)


# The sparse feed-forward of ``layer_1`` of each family's tiny model
# at ``PRNGKey(0)``, from the tree before the three wrappers became
# ``layers.SparseFFN``: the count of the model's leaves, then a leaf's
# shape, its first, middle and last element and the sum of its absolute
# values (all float32).
ROUTED = {
    "down": ((4, 32, 64), (0.16510091722011566, -0.279695600271225,
                           0.24539902806282043), 1196.1374238370556),
    "expert_bias": ((8,), (0.0, 0.0, 0.0), 0.0),
    "gate": ((4, 64, 32), (-0.039273202419281006, 0.056247320026159286,
                           -0.09837953001260757), 838.1156781234095),
    "router": ((64, 8), (0.23169395327568054, 0.09492149204015732,
                         -0.11069563776254654), 53.5094225925277),
    "up": ((4, 64, 32), (-0.008727035485208035, -0.08666466176509857,
                         -0.20660416781902313), 849.4391472818616)}
SPARSE = {
    "lfm2": (lfm2.LFM2LMHeadModel, 33, ROUTED),
    "deepseek_v3": (deepseek_v3.DeepseekV3LMHeadModel, 43, {
        **ROUTED,
        "shared/gate/kernel": ((64, 64), (
            0.03152559697628021, 0.25578877329826355,
            -0.23345838487148285), 421.7913983211464),
        "shared/out/kernel": ((64, 64), (
            0.12082657217979431, 0.18951106071472168,
            0.0615156851708889), 423.91274194295875),
        "shared/up/kernel": ((64, 64), (
            0.27175700664520264, 0.13869033753871918,
            0.08660632371902466), 415.84565084161295)}),
    # no selection bias: the stacks are drawn one place earlier
    "qwen3_next": (qwen3_next.Qwen3NextLMHeadModel, 67, {
        "down": ((4, 32, 64), (-0.012341891415417194, -0.12256232649087906,
                               -0.29218238592147827), 1201.2882740791629),
        "gate": ((4, 64, 32), (-0.039888784289360046, -0.06079043820500374,
                               -0.17548894882202148), 838.9897487502603),
        "router": ((64, 16), (0.23169395327568054, -0.008840334601700306,
                              -0.145328551530838), 105.86670257040532),
        "shared/gate/kernel": ((64, 32), (
            0.03152559697628021, 0.11951718479394913,
            -0.012489289976656437), 208.81371868516624),
        "shared/out/kernel": ((32, 64), (
            0.17087456583976746, -0.1320846974849701,
            -0.21136608719825745), 299.65212143286044),
        "shared/up/kernel": ((64, 32), (
            0.27175700664520264, -0.01454112958163023,
            -0.06476607918739319), 205.3316530324082),
        "shared_gate/kernel": ((64, 1), (
            -0.2501727044582367, -0.0229219701141119,
            0.002174657303839922), 6.707335674203932),
        "up": ((4, 64, 32), (-0.039273202419281006, 0.056247320026159286,
                             -0.09837953001260757), 838.1156781234095)}),
}


@pytest.mark.parametrize("family", list(SPARSE))
def test_the_one_sparse_module_gives_each_familys_parameters(family):
    """``layers.SparseFFN`` built as the family builds it, inside the
    family's model: the paths, shapes, dtypes and drawn values of the
    three wrappers it replaced (a parameter's value hangs on its path
    and on its place among its module's parameters)."""
    model, leaves, want = SPARSE[family]
    cfg = FAMILIES[family]["tiny"]()
    params = jax.jit(model(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 64), jnp.int32))["params"]
    assert len(jax.tree.leaves(params)) == leaves
    got = flatten_dict(params["layer_1"]["moe"], sep="/")
    assert sorted(got) == sorted(want)
    for path, (shape, samples, total) in want.items():
        leaf = np.asarray(got[path])
        assert (leaf.shape, leaf.dtype) == (shape, np.float32), path
        flat = leaf.ravel()
        np.testing.assert_allclose(
            flat[[0, flat.size // 2, -1]], samples, rtol=1e-6, err_msg=path)
        np.testing.assert_allclose(np.abs(flat.astype(np.float64)).sum(),
                                   total, rtol=1e-6, err_msg=path)


def test_lfm2_takes_a_given_choice_as_the_other_two_do():
    """The one module reads the ``given`` collection in every family:
    LFM2's layers given their own choice compute what they compute
    alone, and given another they compute on it."""
    cfg = lfm2.lfm2_tiny_config(dtype=jnp.float32)
    model = lfm2.LFM2LMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                             cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids)["params"]
    own = lfm2.expert_choices(cfg, params, ids)
    assert sorted(own) == [1, 2]
    apply = jax.jit(lambda given: model.apply({"params": params, **given},
                                             ids))
    alone = apply({})
    np.testing.assert_array_equal(apply(layers.given_choices(own)), alone)
    other = {i: (c + 1) % cfg.num_experts for i, c in own.items()}
    assert np.abs(apply(layers.given_choices(other)) - alone).max() > 1e-3
    counts = lfm2.choice_counts(cfg, params, ids)
    assert int(counts[1].sum()) == ids.size * cfg.num_experts_per_tok


MODELS = os.path.dirname(layers.__file__)
FAMILY_MODULES = ["gpt", "granite", "lfm2", "deepseek_v3", "qwen3_next",
                  "afmoe", "keye_vl"]


@pytest.mark.parametrize("module", FAMILY_MODULES + ["layers"])
def test_no_family_imports_another(module):
    """A family's module takes what it shares from ``layers.py`` (and
    ``ops/``, ``parallel/``), never from another family's; and
    ``layers.py`` from none of them."""
    with open(os.path.join(MODELS, module + ".py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            source = "." * node.level + (node.module or "")
            imported.add(source)
            imported.update("%s.%s" % (source, alias.name)
                            for alias in node.names)
    others = [name for name in FAMILY_MODULES if name != module]
    taken = {name for name in imported for other in others
             if name.split(".")[-1] == other}
    assert not taken, taken
