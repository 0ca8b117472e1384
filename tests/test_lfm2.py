"""LFM2-MoE in the program: the dropless routed-experts layer against
the dense gate-matrix form, the rotation and the norm on queries and
keys, the choice kept across ``remat``, the step's gauges, and the step
on a dp x tp mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import layers, lfm2
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.training import lfm2_step_loss, make_lfm2_train_step

TOKENS, HIDDEN, WIDTH, EXPERTS, HELD = 64, 16, 24, 8, 4


def dense_form(x, router, bias, gate, up, down, first_expert, top_k):
    """No dispatch: a ``[T, E]`` gate matrix that is zero off the
    chosen, every expert held over every token times its column."""
    scores = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, chosen, -1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    matrix = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(gates)
    y = 0.0
    for e in range(gate.shape[0]):
        expert = (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e]
        y = y + matrix[:, first_expert + e, None] * expert
    return y


def layer_inputs(seed=0, bias=None, experts=EXPERTS):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    stacked = lambda key, a, b: 0.3 * jax.random.normal(key, (HELD, a, b))
    return (jax.random.normal(keys[0], (TOKENS, HIDDEN)),
            jax.random.normal(keys[1], (HIDDEN, experts)),
            jnp.zeros(experts) if bias is None else bias,
            stacked(keys[2], HIDDEN, WIDTH), stacked(keys[3], HIDDEN, WIDTH),
            stacked(keys[4], WIDTH, HIDDEN))


def both_forms(args, first_expert, top_k, kept=None):
    """Value and gradients (tokens, router, the three stacks) of the
    squared output, the program's layer and the dense form; with
    ``kept`` the program's layer is recomputed in its backward pass but
    for those names."""
    def layer(*a):
        return moe.routed_experts(*a, first_expert=first_expert,
                                  top_k=top_k)[0]
    if kept is not None:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.save_only_these_names(
                *kept))

    def program(*a):
        return (layer(*a) ** 2).sum()

    def dense(*a):
        return (dense_form(*a, first_expert, top_k) ** 2).sum()
    wrt = (0, 1, 3, 4, 5)
    with jax.default_matmul_precision("highest"):
        return (jax.jit(jax.value_and_grad(program, argnums=wrt))(*args),
                jax.jit(jax.value_and_grad(dense, argnums=wrt))(*args))


def assert_same(got, want):
    (got_value, got_grads), (want_value, want_grads) = got, want
    assert float(got_value) == pytest.approx(float(want_value), rel=1e-5)
    # With one expert a token its normalised gate is s / (s + 1e-6): the
    # router's gradient is a millionth of the others', and rounding.
    floor = 1e-7 * abs(float(want_value))
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=2e-5 * float(np.abs(w).max()) + floor)


@pytest.mark.parametrize("first_expert", [0, 4], ids=["first0", "first4"])
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_routed_experts_equal_the_dense_form(top_k, first_expert):
    """Forward and every gradient, whichever run of experts is held."""
    assert_same(*both_forms(layer_inputs(), first_expert, top_k))


@pytest.mark.parametrize("held_only", [True, False],
                         ids=["buffer-full", "buffer-empty"])
def test_row_buffer_full_and_empty(held_only):
    """A selection bias that sends every token to experts held only
    fills the row buffer to its last row; one that sends every token
    elsewhere leaves it empty, and the layer gives zeros and zero
    gradients.  Both equal the dense form."""
    first, top_k = 4, 2
    here = (jnp.arange(EXPERTS) >= first) == held_only
    args = layer_inputs(seed=1, bias=jnp.where(here, 10.0, 0.0))
    routing = moe.sigmoid_top_k(*args[:3], top_k)
    plan, _ = moe.held_pairs(routing, first, HELD)
    rows = moe.dispatch_rows(TOKENS, top_k, HELD)
    assert plan.token.shape == (rows,) == (TOKENS * top_k,)
    assert int(plan.group_sizes.sum()) == (rows if held_only else 0)
    got, want = both_forms(args, first, top_k)
    assert_same(got, want)
    if not held_only:
        assert float(got[0]) == 0.0
        assert all(float(jnp.abs(g).max()) == 0.0 for g in got[1])


@pytest.mark.parametrize("seed,skew", [(0, 0.0), (1, 3.0), (2, 30.0)],
                         ids=["even", "skewed", "one-expert-takes-all"])
def test_no_pair_is_dropped(seed, skew):
    """The rows the plan walks are exactly the (token, expert) pairs
    whose expert is held, each once, whatever the load: with a bias of
    30 on one expert every token sends it a pair, four times the mean,
    and the buffer (no capacity) takes them all."""
    first, top_k = 2, 4
    bias = jnp.zeros(EXPERTS).at[3].set(skew)
    routing = moe.sigmoid_top_k(*layer_inputs(seed, bias)[:3], top_k)
    plan, row_gate = moe.held_pairs(routing, first, HELD)
    sizes = np.asarray(plan.group_sizes)
    chosen = np.asarray(routing.chosen)
    want = sorted((e - first, t) for t in range(TOKENS) for e in chosen[t]
                  if first <= e < first + HELD)
    expert_of_row = np.repeat(np.arange(HELD), sizes)
    got = list(zip(expert_of_row, np.asarray(plan.token)[:sizes.sum()]))
    assert got == want and len(want) == sizes.sum()
    if skew == 30.0:
        assert sizes[3 - first] == TOKENS
    # every row past the pairs weighs nothing, and every pair's place
    # in the sort is its row
    assert (np.asarray(row_gate)[sizes.sum():] == 0).all()
    assert (np.asarray(row_gate)[:sizes.sum()] > 0).all()
    assert np.asarray(plan.valid).sum() == sizes.sum()
    place, is_held = np.asarray(plan.place), np.asarray(plan.is_held)
    assert sorted(place[is_held]) == list(range(sizes.sum()))
    assert (np.asarray(plan.token)[place[is_held]]
            == np.nonzero(is_held)[0]).all()


# Sixteen experts of which four are held, top 2 of 64 tokens: a buffer
# of 128 rows of which an even router fills 32.
WALK_EXPERTS, WALK_FIRST, WALK_TOP_K = 16, 4, 2
WALK_ROWS, WALK_EXPECTED = TOKENS * WALK_TOP_K, TOKENS * WALK_TOP_K * HELD // 16
ROUTINGS = ["nothing-held", "all-held", "ends-on-a-chunks-boundary",
            "ends-one-row-past-it", "three-times-the-expectation"]


def walked_routing(monkeypatch, routing):
    """The layer's inputs for one of ``ROUTINGS`` and the pairs it
    sends the experts held, with the walk's chunk set so that the
    buffer is several chunks long and the pairs end where the case
    says.  The chunk follows the routing, not the other way round: the
    router decides what is held, and a boundary is wherever it ends."""
    here = ((jnp.arange(WALK_EXPERTS) >= WALK_FIRST)
            & (jnp.arange(WALK_EXPERTS) < WALK_FIRST + HELD))
    bias = {"nothing-held": jnp.where(here, -10.0, 0.0),
            "all-held": jnp.where(here, 10.0, 0.0),
            "three-times-the-expectation": jnp.where(here, 0.72, 0.0)}.get(
                routing)
    args = layer_inputs(seed=3, bias=bias, experts=WALK_EXPERTS)
    plan, _ = moe.held_pairs(moe.sigmoid_top_k(*args[:3], WALK_TOP_K),
                             WALK_FIRST, HELD)
    pairs = int(plan.group_sizes.sum())
    chunk = 24
    if routing in ROUTINGS[2:4]:
        ends = pairs - (routing == "ends-one-row-past-it")
        chunk = max(d for d in range(2, WALK_ROWS // 4) if ends % d == 0)
    monkeypatch.setattr(moe, "WALK_CHUNK_ROWS", chunk)
    return args, pairs, chunk


@pytest.mark.parametrize("kept", [
    None, (moe.CHOICE_NAME, moe.ROWS_NAME, moe.EXPERT_GATE_UP_NAME),
    (moe.CHOICE_NAME,)], ids=["plain", "remat-kept", "remat-dropped"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_walk_stops_where_the_pairs_end_and_drops_none(monkeypatch, routing,
                                                       kept):
    """Wherever the pairs end in the buffer (nowhere, at its last row,
    on a chunk's last row of the walk, one row into the next chunk, at
    three times what an even router sends) the layer and every gradient
    equal the dense form: plain, and recomputed with the sorted rows and
    the first products' outputs kept or made again."""
    args, pairs, chunk = walked_routing(monkeypatch, routing)
    assert WALK_ROWS > 4 * chunk
    if routing == "three-times-the-expectation":
        assert 3 * WALK_EXPECTED <= pairs < WALK_ROWS
    elif routing in ROUTINGS[:2]:
        assert pairs == {"nothing-held": 0, "all-held": WALK_ROWS}[routing]
    else:
        assert chunk < pairs < WALK_ROWS - chunk
        assert pairs % chunk == (routing == "ends-one-row-past-it")
    got, dense = both_forms(args, WALK_FIRST, WALK_TOP_K, kept)
    assert_same(got, dense)
    if pairs == 0:
        assert float(got[0]) == 0.0
        assert all(float(jnp.abs(g).max()) == 0.0 for g in got[1])


@pytest.mark.parametrize("routing", ROUTINGS[2:])
def test_rows_past_the_pairs_may_hold_anything(monkeypatch, routing):
    """The chip's grouped kernel writes nothing past the groups' ends,
    so there its output, and the cotangent it hands its left operand,
    hold what the memory held.  With nan there, forward and backward,
    every gradient is finite and the dense form's."""
    args, pairs, chunk = walked_routing(monkeypatch, routing)
    product = jax.lax.ragged_dot

    @jax.custom_vjp
    def spoil_past(rows, sizes):
        row = jnp.arange(rows.shape[0])[:, None]
        return jnp.where(row < sizes.sum(), rows, jnp.nan)
    spoil_past.defvjp(lambda rows, sizes: (spoil_past(rows, sizes), None),
                      lambda _, d: (d, None))

    @jax.custom_vjp
    def spoil_back(rows, sizes):
        return rows
    spoil_back.defvjp(lambda rows, sizes: (rows, sizes),
                      lambda sizes, d: (spoil_past(d, sizes), None))

    def spoiled(lhs, rhs, group_sizes, **options):
        return spoil_past(product(spoil_back(lhs, group_sizes), rhs,
                                  group_sizes, **options), group_sizes)
    plan, _ = moe.held_pairs(moe.sigmoid_top_k(*args[:3], WALK_TOP_K),
                             WALK_FIRST, HELD)
    probe = spoiled(jnp.ones((WALK_ROWS, HIDDEN)), args[3], plan.group_sizes)
    assert bool(jnp.isnan(probe[pairs:]).all()) and pairs < WALK_ROWS
    monkeypatch.setattr(jax.lax, "ragged_dot", spoiled)
    got, dense = both_forms(args, WALK_FIRST, WALK_TOP_K)
    assert all(bool(jnp.isfinite(g).all()) for g in got[1])
    assert_same(got, dense)


def test_the_traced_program_holds_the_chunks_as_one_loop(monkeypatch):
    """Sixteen chunks or two: three grouped products, one loop (the
    rows' gates) and as many gathers, forward and backward."""
    args = layer_inputs(seed=3, experts=WALK_EXPERTS)

    def counts(chunk):
        monkeypatch.setattr(moe, "WALK_CHUNK_ROWS", chunk)
        layer = lambda *a: moe.routed_experts(
            *a, first_expert=WALK_FIRST, top_k=WALK_TOP_K)[0]
        grads = jax.grad(lambda *a: (layer(*a) ** 2).sum(),
                         argnums=(0, 1, 3, 4, 5))
        return [(text.count("= ragged_dot"), text.count(" gather["),
                 text.count(" while["))
                for text in (str(jax.make_jaxpr(layer)(*args)),
                             str(jax.make_jaxpr(grads)(*args)))]
    sixteen, two = counts(WALK_ROWS // 16), counts(WALK_ROWS // 2)
    assert sixteen == two
    (products, _, loops), (all_products, _, all_loops) = sixteen
    assert (products, all_products) == (3, 9)
    assert loops == all_loops == 1


def test_rows_walked_is_the_chunks_that_start_before_the_pairs_end():
    chunk = moe.WALK_CHUNK_ROWS
    assert chunk % 512 == 0          # whole tiles of the grouped kernel
    rows = 16 * chunk
    assert moe.rows_walked(0, rows) == 0
    assert moe.rows_walked(1, rows) == chunk
    assert moe.rows_walked(4 * chunk, rows) == 4 * chunk
    assert moe.rows_walked(4 * chunk + 1, rows) == 5 * chunk
    assert moe.rows_walked(rows, rows) == rows
    # the cell: 8012 to 8478 pairs a layer of 32768 rows
    assert moe.rows_walked(8012, 32768) == 4 * 2048
    assert moe.rows_walked(8478, 32768) == 5 * 2048
    # a buffer shorter than a chunk, or no whole number of them
    assert moe.rows_walked(3, 100) == 100
    assert moe.rows_walked(chunk + 1, chunk + 100) == chunk + 100


def test_dispatch_bytes_counts_the_arrays_by_hand():
    # 8192 tokens at the published widths, top 4 with 16 held, bf16:
    # 32768 rows; rows and output [R, 2048], gate, up and their product
    # [R, 1536]; choice, gates and places [T, 4], token and gate of a
    # row, sixteen sizes.
    rows = 8192 * 4
    assert moe.dispatch_rows(8192, 4, 16) == rows
    assert moe.dispatch_rows(8192, 4, 2) == 8192 * 2   # two held: two pairs
    want = (2 * rows * (2 * 2048 + 3 * 1536)
            + 8192 * 4 * 12 + rows * 8 + 16 * 4)
    assert moe.dispatch_bytes(8192, 2048, 1536, 4, 16, 2) == want


def test_rotation_equals_the_complex_form():
    """Channels ``i`` and ``i + d / 2`` of a head are the real and the
    imaginary part of a number turned by ``t * theta^(-2i / d)``."""
    seq, heads, d, theta = 24, 3, 16, 1e6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, heads, d))
    got = layers.rotate(x, *layers.rotary_tables(seq, d, theta))
    x64 = np.asarray(x, np.float64)
    z = x64[..., :d // 2] + 1j * x64[..., d // 2:]
    angle = (np.arange(seq)[:, None, None]
             * theta ** (-np.arange(0, d, 2) / d))
    turned = z * np.exp(1j * angle)
    want = np.concatenate([turned.real, turned.imag], -1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # position 0 is not turned, and a turn keeps a pair's length
    np.testing.assert_allclose(np.asarray(got[:, 0]), x64[:, 0], atol=1e-6)
    np.testing.assert_allclose(np.abs(turned), np.abs(z), rtol=1e-12)


@pytest.mark.parametrize("which", ["query", "key"])
def test_queries_and_keys_are_normed_over_their_head(which):
    """With the norm a head's queries (keys) have a fixed length, so a
    projection three times as large gives the same attention (but for
    ``eps``); one weight of ``head_dim`` serves every head."""
    cfg = lfm2.lfm2_tiny_config(dtype=jnp.float32)
    attention = lfm2.RotaryAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    tables = layers.rotary_tables(32, cfg.head_dim, cfg.rope_theta)
    params = attention.init(jax.random.PRNGKey(1), x, *tables)["params"]
    assert params[which + "_norm"]["scale"].shape == (cfg.head_dim,)
    larger = dict(params, **{which: {"kernel": 3.0 * params[which]["kernel"]}})
    got = attention.apply({"params": larger}, x, *tables)
    want = attention.apply({"params": params}, x, *tables)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3,
                               atol=1e-5)


def test_config_refuses_what_the_model_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.lfm2_tiny_config(layer_types=("conv", "mamba", "conv"))
    with pytest.raises(ValueError, match="ffn_types"):
        lfm2.lfm2_tiny_config(ffn_types=("dense", "sparse"))
    with pytest.raises(ValueError, match="experts held"):
        lfm2.lfm2_tiny_config(first_expert=6, experts_held=4)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        lfm2.lfm2_tiny_config(num_attention_heads=4, num_key_value_heads=3)


def _tiny_step(axes, **config):
    cfg = lfm2.lfm2_tiny_config(dtype=jnp.float32, **config)
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, jax.devices()[:chips])
    init_fn, step_fn, batch_sharding = make_lfm2_train_step(cfg, mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                             cfg.vocab_size)
    return cfg, mesh, init_fn, step_fn, jax.device_put(ids, batch_sharding)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_step_loss_on_dp_by_tp_equals_one_device(remat):
    """``make_lfm2_train_step`` under ``lfm2_partition_rules`` on dp2 x
    tp2: the loss the step returns is the one-device loss of the same
    parameters; the heads, the dense SwiGLU's and every expert's
    columns and the embedding's rows are split over ``tp``; the router
    is whole."""
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2},
                                                  remat=remat)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    spec = lambda *path: tuple(jax.tree_util.tree_reduce(
        lambda t, k: t[k], path, params).sharding.spec)
    assert spec("layer_1", "attention", "key", "kernel")[1] == "tp"
    assert spec("layer_0", "mlp", "gate", "kernel")[1] == "tp"
    assert spec("layer_0", "conv", "out_proj", "kernel")[0] == "tp"
    assert "tp" not in spec("layer_0", "conv", "in_proj", "kernel")
    assert spec("layer_1", "moe", "gate")[2] == "tp"
    assert spec("layer_1", "moe", "down")[1] == "tp"
    assert "tp" not in spec("layer_1", "moe", "router")
    assert spec("word_embeddings", "embedding")[0] == "tp"
    host = jax.device_get(params)
    want = lfm2_step_loss(lfm2.LFM2LMHeadModel(cfg), host,
                          jax.device_get(ids))
    new_params, _, loss = step_fn(params, opt_state, ids)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # AdamW moved every leaf but the selection bias, which no gradient
    # reaches and no decay touches.
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         new_params, host)
    for layer in ("layer_1", "layer_2"):
        assert moved[layer]["moe"].pop("expert_bias") == 0.0
    assert all(v > 0 for v in jax.tree.leaves(moved))


def test_experts_lie_on_ep_where_the_mesh_has_one():
    from horovod_tpu.parallel.sharding import (infer_shardings,
                                               lfm2_partition_rules)
    cfg = lfm2.lfm2_tiny_config()
    ids = jnp.zeros((2, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: lfm2.LFM2LMHeadModel(cfg).init(
        jax.random.PRNGKey(0), ids)["params"])
    mesh = build_mesh({"dp": 2, "ep": 2}, jax.devices()[:4])
    shardings = infer_shardings(shapes, mesh, lfm2_partition_rules())
    experts = shardings["layer_1"]["moe"]
    assert tuple(experts["gate"].spec)[0] == "ep"
    assert tuple(experts["down"].spec)[0] == "ep"
    assert "ep" not in tuple(experts["router"].spec)


def test_gauges_show_in_the_metrics_snapshot():
    cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 2, "tp": 2})
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    step_fn.lower(*state, ids)
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_moe_experts"] == {"which=total": 8.0,
                                         "which=held": 4.0}
    assert gauges["hvd_moe_top_k"] == 2
    # one device's share of the batch: 2 of 4 sequences of 64
    assert gauges["hvd_moe_dispatch_rows"] == 128 * 2
    assert gauges["hvd_moe_walk_chunk_rows"] == moe.WALK_CHUNK_ROWS
    # a layer's walked share, as docs/observability.md reads it: a
    # buffer shorter than a chunk is walked whole once a pair is held
    assert moe.rows_walked(1, int(gauges["hvd_moe_dispatch_rows"])) == 256
    assert gauges["hvd_moe_dispatch_bytes"] == moe.dispatch_bytes(
        128, cfg.hidden_size, cfg.moe_intermediate_size, 2, 4, 4)
    layers = gauges["hvd_hybrid_layers"]
    assert (layers["kind=conv"], layers["kind=full_attention"]) == (2.0, 1.0)


def test_choice_counts_of_a_batch():
    cfg = lfm2.lfm2_tiny_config(dtype=jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                             cfg.vocab_size)
    params = lfm2.LFM2LMHeadModel(cfg).init(jax.random.PRNGKey(1),
                                            ids)["params"]
    counts = lfm2.choice_counts(cfg, params, ids)
    assert sorted(counts) == [1, 2]          # the two sparse layers
    for of_layer in counts.values():
        assert of_layer.shape == (8,) and int(of_layer.sum()) == 128 * 2
    chosen = lfm2.expert_choices(cfg, params, ids)[1]
    assert chosen.shape == (128, 2) and chosen.dtype == jnp.int32
    assert (np.asarray(counts[1]) == np.bincount(
        np.asarray(chosen).reshape(-1), minlength=8)).all()


# The benchmark's cell: 8192 tokens, the dense layer and one period at
# the published widths, 9.46 GB of parameters and AdamW's moments, a
# v5e's memory.
CELL = dict(vocab_size=16384,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            ffn_types=("dense",) + ("sparse",) * 4, experts_held=16)


def test_remat_bytes_by_hand_at_the_published_widths():
    """A token's bytes a name at the cell's widths (what the device
    keeps of them is ``test_causal_lm_families.py``'s)."""
    cfg = lfm2.LFM2Config(**CELL)
    rows = 4 * 4   # of one token: four sparse layers, top 4, 16 held
    per_token = {"flash_out": 2048 * 2, "flash_lse": 32 * 4,
                 "moe_chosen": 4 * 4 * 4, "gate_up": 2 * 11776 * 2,
                 "moe_gate_up": rows * 2 * 1536 * 2,
                 "in_proj": 4 * 3 * 2048 * 2, "moe_rows": rows * 2048 * 2}
    assert tuple(per_token) == lfm2.REMAT_NAMES
    for name, width in per_token.items():
        assert lfm2.remat_bytes((name,), 2, 4096, cfg) == 8192 * width


def test_the_choice_is_kept_across_remat():
    """A recomputed layer does not choose again: the step with
    ``remat`` holds as many ``top_k`` as the step without, one a sparse
    layer, while its matmuls are traced a second time where the device
    reports little memory."""
    def text(remat, limit=None):
        cfg, mesh, init_fn, step_fn, ids = _tiny_step({"dp": 1},
                                                      remat=remat)
        state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
        return str(jax.make_jaxpr(step_fn)(*state, ids))
    plain, kept = text(False), text(True)
    assert plain.count(" top_k[") == kept.count(" top_k[") == 2
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr("horovod_tpu.training._memory_limit",
                      lambda device: 1 << 20)
        small = text(True)
    assert small.count(" top_k[") == 2
    assert small.count("ragged_dot") > kept.count("ragged_dot")
    names = hvd.metrics_snapshot()["gauges"]["hvd_remat_kept_bytes"]
    assert "family=lfm2,names=" + "+".join(lfm2.KEPT_NAMES) in names


def test_flash_path_equals_the_einsum_path_with_rotary_heads():
    """The kernels (interpret mode, under ``jax.jit``, a toy size) on
    normed, rotated queries and keys, the keys and values repeated to
    the query heads, give the grouped einsums' logits."""
    from jax.experimental.pallas import tpu as pltpu
    cfg = lfm2.lfm2_tiny_config(dtype=jnp.float32,
                                layer_types=("full_attention",),
                                ffn_types=("dense",))
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 32), 0,
                             cfg.vocab_size)
    einsum = lfm2.LFM2LMHeadModel(cfg)
    params = einsum.init(jax.random.PRNGKey(1), ids)["params"]
    flash = lfm2.LFM2LMHeadModel(
        dataclasses.replace(cfg, attention_impl="flash"))
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(flash.apply)({"params": params}, ids)
    want = einsum.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
