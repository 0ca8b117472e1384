"""The routed experts' wide passes as Pallas kernels that stop where the
pairs end, in the interpreter on the CPU (the same bodies compile via
Mosaic on a TPU; ``tests/test_chip_compile.py`` compiles them for one):
each kernel against the XLA form wherever the pairs end, with nan in
everything the count excludes; the layer through the kernels against
the layer through XLA's passes; and what a process pays to set them up:
a body is traced once however many layers and programs call it.
"""

import functools
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

import horovod_tpu as hvd
from horovod_tpu.models import lfm2
from horovod_tpu.ops import pallas_moe
from horovod_tpu.parallel import moe

# 64 tokens, top 2 of 8 experts of which 4 are held: a buffer of 128
# rows; the kernels take 32 rows and 16 tokens a grid step.
TOKENS, TOP_K, EXPERTS, FIRST, HELD = 64, 2, 8, 2, 4
ROWS, ROW_BLOCK, TOKEN_BLOCK, WIDTH = TOKENS * TOP_K, 32, 16, 256
# The TPU interpreter: memory spaces, DMAs and semaphores simulated,
# and memory nothing wrote reads nan.
TPU = pltpu.InterpretParams()
COUNTS = {"none": 0, "all": ROWS, "a-blocks-boundary": 2 * ROW_BLOCK,
          "one-row-past-it": 2 * ROW_BLOCK + 1, "mid-block": 37}
DTYPES = {"float32": (jnp.float32, 1024), "bfloat16": (jnp.bfloat16, 2048)}
cases = pytest.mark.parametrize("pairs", list(COUNTS), ids=list(COUNTS))
dtypes = pytest.mark.parametrize("dtype", list(DTYPES))
KERNELS = ("pack_rows", "rows_of_tokens", "tokens_of_rows", "add_rows",
           "gated", "gated_bwd")
GROUPED = ("grouped_rows", "grouped_rows_t", "grouped_weights")


def plan_with(pairs: int, seed=0, held=HELD, rows=ROWS):
    """A plan in which exactly ``pairs`` of the pairs are held: the
    first ``pairs`` of a shuffle choose an expert held, the rest one
    that is not (``held`` 1: a token's two choices cannot both be held,
    and the buffer is ``TOKENS`` rows)."""
    rng = np.random.default_rng(seed)
    if held == 1:
        keep = np.zeros((TOKENS, TOP_K), bool)
        keep[rng.permutation(TOKENS)[:pairs], 0] = True
        keep = keep.reshape(-1)
    else:
        keep = np.zeros(TOKENS * TOP_K, bool)
        keep[rng.permutation(TOKENS * TOP_K)[:pairs]] = True
    others = EXPERTS - held
    chosen = np.where(
        keep, FIRST + rng.integers(0, held, keep.size),
        (FIRST + held + rng.integers(0, others - 1, keep.size)) % EXPERTS)
    if held != 1:
        # a token's two choices are two experts
        chosen = chosen.reshape(TOKENS, TOP_K)
        same = chosen[:, 0] == chosen[:, 1]
        flip = np.where(keep.reshape(TOKENS, TOP_K)[:, 1],
                        FIRST + (chosen[:, 1] - FIRST + 1) % held,
                        (chosen[:, 1] + 1 - FIRST - held) % others
                        + FIRST + held)
        chosen[:, 1] = np.where(same, flip % EXPERTS, chosen[:, 1])
    gates = rng.uniform(0.1, 1.0, keep.size).astype(np.float32)
    routing = moe.Routing(
        jnp.asarray(chosen.reshape(TOKENS, TOP_K), jnp.int32),
        jnp.asarray(gates.reshape(TOKENS, TOP_K)))
    plan, row_gate = moe.held_pairs(routing, FIRST, held)
    assert plan.token.shape == (rows,)
    assert int(plan.group_sizes.sum()) == pairs
    return plan, row_gate


def count_of(plan):
    return plan.group_sizes.sum()


def normal(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def spoil_past(x, pairs):
    return x.at[pairs:].set(jnp.nan)


def f32(x):
    return np.asarray(x.astype(jnp.float32))


def test_the_plan_lists_a_tokens_pairs_held_first():
    plan, _ = plan_with(37)
    place, is_held = np.asarray(plan.place), np.asarray(plan.is_held)
    held_place, count = map(np.asarray, moe._pairs_held_first(plan))
    assert (count == is_held.sum(1)).all() and count.sum() == 37
    for t in range(TOKENS):
        assert list(held_place[t, :count[t]]) == list(place[t][is_held[t]])


@dtypes
@cases
def test_rows_of_tokens_stops_where_the_pairs_end(dtype, pairs):
    """Rows that hold a pair are the gather's, bit for bit; no block
    after the last that holds one is written (the interpreter leaves
    nan there); and a token no pair held names may be nan: it is never
    fetched."""
    dtype, hidden = DTYPES[dtype]
    n = COUNTS[pairs]
    plan, _ = plan_with(n)
    x = normal(1, (TOKENS, hidden), dtype)
    x = jnp.where(plan.is_held.any(1)[:, None], x, jnp.nan)
    got = f32(pallas_moe.rows_of_tokens(
        x, plan.token, count_of(plan), block=ROW_BLOCK, in_flight=4,
        interpret=TPU))
    want = f32(moe._dispatch(x, plan, False)[0])
    assert np.isfinite(got[:n]).all()
    np.testing.assert_array_equal(got[:n], want[:n])
    written = -(-n // ROW_BLOCK) * ROW_BLOCK
    if n:       # with no pair the one block written back is never filled
        assert np.isnan(got[written:]).all()


@dtypes
def test_packing_by_xla_is_the_kernels_packing(dtype):
    dtype, hidden = DTYPES[dtype]
    x = normal(1, (TOKENS, hidden), dtype)
    np.testing.assert_array_equal(
        np.asarray(pallas_moe.packed_by_xla(x)),
        np.asarray(pallas_moe.pack_rows(x, TOKENS, block=ROW_BLOCK,
                                        interpret=TPU)))


@dtypes
@cases
def test_tokens_of_rows_sums_the_pairs_held(dtype, pairs):
    """With nan in every row past the pairs, every token's sum is the
    XLA form's, zeros for a token with no pair held."""
    dtype, hidden = DTYPES[dtype]
    n = COUNTS[pairs]
    plan, _ = plan_with(n, seed=1)
    out = spoil_past(normal(2, (ROWS, hidden), dtype), n)
    got = f32(pallas_moe.tokens_of_rows(
        out, *moe._pairs_held_first(plan), count_of(plan),
        block=TOKEN_BLOCK, row_block=ROW_BLOCK, in_flight=4, interpret=TPU))
    np.testing.assert_array_equal(got, f32(moe._combine(out, plan, False)))
    assert (got[~np.asarray(plan.is_held).any(1)] == 0).all()


@dtypes
@cases
def test_add_rows_adds_the_rows_that_hold_a_pair(dtype, pairs):
    dtype, hidden = DTYPES[dtype]
    n = COUNTS[pairs]
    a, b = (spoil_past(normal(s, (ROWS, hidden), dtype), n) for s in (3, 4))
    got = f32(pallas_moe.add_rows(a, b, n, block=ROW_BLOCK, interpret=TPU))
    np.testing.assert_array_equal(got[:n], f32(a + b)[:n])


def masked_product(a, b, row_gate, plan):
    keep = plan.valid[:, None]
    gated = jax.nn.silu(jnp.where(keep, a, 0)) * jnp.where(keep, b, 0)
    return (gated.astype(jnp.float32) * row_gate[:, None]).astype(a.dtype)


# What a result of the gated product may differ by from the float32
# oracle: float32's own roundings, or one rounding of a bfloat16 result
# (XLA's CPU rounds bfloat16 after every operation, the kernels and the
# TPU's fusions once, so the XLA form in bfloat16 is no oracle).
ROUNDING = {jnp.float32: dict(rtol=1e-6, atol=1e-6),
            jnp.bfloat16: dict(rtol=2 ** -8, atol=2 ** -8)}


@dtypes
@cases
def test_gated_product_and_its_backward(dtype, pairs):
    """``silu(a) * b * gate`` and the three cotangents over the rows
    that hold a pair, nan past them in both operands and the cotangent."""
    dtype, _ = DTYPES[dtype]
    n = COUNTS[pairs]
    plan, row_gate = plan_with(n, seed=2)
    a, b, d = (spoil_past(normal(s, (ROWS, WIDTH), dtype), n)
               for s in (3, 4, 5))
    want, transpose = jax.vjp(
        lambda a, b, g: masked_product(a, b, g, plan),
        a.astype(jnp.float32), b.astype(jnp.float32), row_gate)
    got = pallas_moe.gated(a, b, row_gate, count_of(plan), block=ROW_BLOCK,
                           interpret=TPU)
    np.testing.assert_allclose(f32(got)[:n], f32(want)[:n],
                               **ROUNDING[dtype])
    grads = pallas_moe.gated_bwd(a, b, row_gate, d, count_of(plan),
                                 block=ROW_BLOCK, interpret=TPU)
    wants = transpose(jnp.where(plan.valid[:, None], d, 0).astype(
        jnp.float32))
    for g, w in zip(grads, wants):
        assert np.isfinite(f32(g)[:n]).all()
        # the gate's cotangent is a sum over the width
        scale = np.abs(f32(w)[:n]).max() if n else 1.0
        tolerance = ROUNDING[dtype]
        np.testing.assert_allclose(
            f32(g)[:n] / scale, f32(w)[:n] / scale,
            rtol=tolerance["rtol"], atol=4 * tolerance["atol"])


def test_a_gated_block_is_the_row_block_at_512_and_halves_as_it_widens():
    assert pallas_moe.gated_block(81920, 512) == pallas_moe.ROW_BLOCK == 512
    assert pallas_moe.gated_block(98304, 768) == 256
    assert pallas_moe.gated_block(32768, 1536) == 128
    assert pallas_moe.gated_block(128, 24) == 128


def test_blocks_walked_is_the_blocks_that_start_before_the_pairs_end():
    block = pallas_moe.ROW_BLOCK
    rows = 16 * block
    assert moe.blocks_walked(0, rows) == 0
    assert moe.blocks_walked(1, rows) == 1
    assert moe.blocks_walked(4 * block, rows) == 4
    assert moe.blocks_walked(4 * block + 1, rows) == 5
    assert moe.blocks_walked(rows, rows) == 16
    # the three cells, at the pairs their routers are expected to send:
    # a quarter, an eighth, a sixteenth of the grid does work
    assert moe.blocks_walked(8192, 32768) * 4 == 32768 // block
    assert moe.blocks_walked(12288, 98304) * 8 == 98304 // block
    assert moe.blocks_walked(5120, 81920) * 16 == 81920 // block
    # a buffer shorter than a block is one block; another kernel's block
    assert moe.blocks_walked(3, 100) == 1
    assert moe.blocks_walked(300, 1024, block=128) == 3


# The grouped products: a buffer of four row tiles of 128 (the tile is
# the kernels' own, of the static shapes alone), six groups; by case the
# rows each group holds.
GROUPED_ROWS, GROUPED_K, GROUPED_N = 512, 128, 128
GROUPS = {
    "ends-inside-a-tile": [100, 30, 70, 126, 9, 0],
    "two-and-three-in-a-tile": [40, 50, 30, 136, 100, 60],
    "an-empty-group-first": [0, 130, 20, 5, 1, 200],
    "an-empty-group-last": [130, 20, 5, 1, 200, 0],
    "empty-groups-in-the-middle": [130, 0, 0, 25, 0, 90],
    "every-pair-in-one-expert": [0, 0, 300, 0, 0, 0],
    "no-pair": [0, 0, 0, 0, 0, 0],
    "ends-at-a-tiles-edge": [128, 60, 68, 0, 0, 0],
    "the-whole-buffer": [128, 1, 127, 200, 56, 0],
}
grouped_cases = pytest.mark.parametrize("groups", list(GROUPS))


def walked_plainly(lhs, weights, d_out, sizes, tile):
    """The three products by a plain walk: every (tile, group) that
    share a row, the tile's product whole and the group's rows taken
    from it; the weights' summed tile by tile.  The same shapes a
    product as the kernels', so the same sums in the same order."""
    rows, ends = lhs.shape[0], np.cumsum(sizes)
    out = np.zeros((rows, weights.shape[2]), np.float32)
    d_lhs = np.zeros(lhs.shape, np.float32)
    d_weights = np.zeros(weights.shape, np.float32)
    row = np.arange(tile)[:, None]
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    for g, size in enumerate(sizes):
        for t in range(rows // tile):
            mine = ((row + t * tile >= ends[g] - size)
                    & (row + t * tile < ends[g]))
            if not mine.any():
                continue
            at = slice(t * tile, (t + 1) * tile)
            out[at] = np.where(
                mine, dot(lhs[at], weights[g], (((1,), (0,)), ((), ()))),
                out[at])
            d_lhs[at] = np.where(
                mine, dot(d_out[at], weights[g], (((1,), (1,)), ((), ()))),
                d_lhs[at])
            d_weights[g] += dot(jnp.where(mine, lhs[at], 0),
                                jnp.where(mine, d_out[at], 0),
                                (((0,), (0,)), ((), ())))
    return out, d_lhs, d_weights


@dtypes
@grouped_cases
def test_grouped_kernels_are_ragged_dot_and_its_transposes(dtype, groups):
    """Each of the three against a plain walk over tiles and groups bit
    for bit, and against ``lax.ragged_dot`` and its two transposes to
    float32's roundings and to one step of bfloat16 (the CPU's
    ``ragged_dot`` sums in another order), with nan in every row past
    the groups' ends; the rows of a touched tile that belong to no
    group are zeros, a tile past them is not written; an expert with no
    row gets a cotangent of zeros."""
    dtype, _ = DTYPES[dtype]
    sizes = np.array(GROUPS[groups])
    n, tile = sizes.sum(), pallas_moe.grouped_tile(GROUPED_ROWS)
    assert tile == pallas_moe.GROUPED_TILE == 128
    lhs, d_out = (spoil_past(normal(s, (GROUPED_ROWS, width), dtype), n)
                  for s, width in ((1, GROUPED_K), (2, GROUPED_N)))
    weights = normal(3, (len(sizes), GROUPED_K, GROUPED_N), dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    walk = pallas_moe.grouped_walk(group_sizes, GROUPED_ROWS)
    got = (pallas_moe.grouped_rows(lhs, weights, walk, interpret=True),
           pallas_moe.grouped_rows_t(d_out, weights, walk, interpret=True),
           pallas_moe.grouped_weights(lhs, d_out, walk, interpret=True))
    clean = lambda x: jnp.where(jnp.arange(GROUPED_ROWS)[:, None] < n, x, 0)
    plain = walked_plainly(clean(lhs), weights, clean(d_out), sizes, tile)
    product, transpose = jax.vjp(
        lambda lhs, weights: jax.lax.ragged_dot(
            lhs, weights, group_sizes, preferred_element_type=dtype),
        clean(lhs), weights)
    ragged = (product, *transpose(clean(d_out)))
    written = -(-n // tile) * tile
    step = 1e-5 if dtype == jnp.float32 else 2 ** -8
    for name, g, p, r in zip(GROUPED, got, plain, ragged):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape, name
        read = len(sizes) if name == "grouped_weights" else n
        g, r = f32(g), f32(r)
        np.testing.assert_array_equal(
            g[:read], f32(jnp.asarray(p).astype(dtype))[:read], name)
        np.testing.assert_allclose(
            g[:read], r[:read], rtol=step,
            atol=step * max(np.abs(r[:read]).max(), 1e-30) if read else 0,
            err_msg=name)
        if name != "grouped_weights":
            assert (g[n:written] == 0).all(), name
    assert (f32(got[2])[sizes == 0] == 0).all()


def test_the_walk_visits_a_shared_tile_once_for_each_group():
    """(row tile, group) pairs in order, an empty group once, the steps
    past the last naming the last again; and the fill that the gauge
    ``hvd_moe_grouped_tile_fill`` reads: rows that hold a pair over the
    rows of the tiles walked for them."""
    sizes = jnp.asarray(GROUPS["ends-inside-a-tile"], jnp.int32)
    walk = pallas_moe.grouped_walk(sizes, GROUPED_ROWS)
    steps = int(walk.steps[0])
    assert list(np.asarray(walk.bounds)) == [0, 100, 130, 200, 326, 335, 335]
    assert list(zip(np.asarray(walk.tile)[:steps],
                    np.asarray(walk.group)[:steps])) == [
        (0, 0), (0, 1), (1, 1), (1, 2), (1, 3), (2, 3), (2, 4), (2, 5)]
    assert walk.group.shape == (pallas_moe.grouped_steps(
        GROUPED_ROWS, 6, 128),) == (4 + 6 - 1,)
    assert (np.asarray(walk.tile)[steps:] == 2).all()
    assert (np.asarray(walk.group)[steps:] == 5).all()
    # seven visits of 128 rows for 335 pairs; the empty group's is none
    assert float(pallas_moe.grouped_tile_fill(sizes, GROUPED_ROWS)) == (
        pytest.approx(335 / (7 * 128)))
    assert float(pallas_moe.grouped_tile_fill(
        jnp.zeros(6, jnp.int32), GROUPED_ROWS)) == 1.0
    # the four cells at the pairs their routers are expected to send,
    # spread evenly: 160, 512, 768 and 1024 rows an expert
    for rows, held, each, fill in ((81920, 32, 160, 160 / 256),
                                   (32768, 16, 512, 1.0),
                                   (98304, 16, 768, 1.0),
                                   (131072, 16, 1024, 1.0)):
        assert float(pallas_moe.grouped_tile_fill(
            jnp.full(held, each, jnp.int32), rows)) == pytest.approx(
                fill, abs=0.05), rows


def test_a_batchs_choices_set_the_tile_fill_gauge():
    from horovod_tpu import training
    chosen = jnp.asarray(np.random.default_rng(0).integers(
        0, EXPERTS, (256, TOP_K)), jnp.int32)
    training.set_grouped_tile_fill({3: chosen}, FIRST, HELD)
    counts = np.bincount(np.asarray(chosen).reshape(-1),
                         minlength=EXPERTS)[FIRST:FIRST + HELD]
    want = float(pallas_moe.grouped_tile_fill(
        jnp.asarray(counts), moe.dispatch_rows(256, TOP_K, HELD)))
    assert 0 < want <= 1
    assert hvd.metrics_snapshot()["gauges"]["hvd_moe_grouped_tile_fill"][
        "layer=3"] == pytest.approx(want)


@pytest.fixture
def kernels(monkeypatch):
    """``parallel/moe.py``'s passes as kernels in the interpreter that
    is pure JAX (``jax.checkpoint`` takes no other), at this file's
    blocks, wherever a caller would find one TPU device."""
    for name, options in (
            ("rows_of_tokens", dict(block=ROW_BLOCK, in_flight=4)),
            ("tokens_of_rows", dict(block=TOKEN_BLOCK, row_block=ROW_BLOCK,
                                    in_flight=4)),
            ("add_rows", dict(block=ROW_BLOCK)),
            ("gated", dict(block=ROW_BLOCK)),
            ("gated_bwd", dict(block=ROW_BLOCK)),
            ("grouped_rows", {}), ("grouped_rows_t", {}),
            ("grouped_weights", {})):
        monkeypatch.setattr(pallas_moe, name, functools.partial(
            getattr(pallas_moe, name), interpret=True, **options))
    monkeypatch.setattr(moe, "on_one_tpu", lambda mesh: True)


@pytest.mark.parametrize("held", [HELD, 1], ids=["4-held", "top-k-over-held"])
@dtypes
@cases
def test_dispatch_and_combine_are_each_others_transpose(kernels, dtype,
                                                        pairs, held):
    """Values and VJPs through the kernels equal the XLA forms', the
    cotangents nan past the pairs as the grouped products leave them;
    with one expert held of a top 2 the buffer is ``T`` rows, not ``2
    T``."""
    dtype, hidden = DTYPES[dtype]
    rows = TOKENS * min(TOP_K, held)
    n = min(COUNTS[pairs], rows)
    plan, _ = plan_with(n, seed=3, held=held, rows=rows)
    x, d_y = (normal(s, (TOKENS, hidden), dtype) for s in (6, 7))
    out, *d_rows = (spoil_past(normal(s, (rows, hidden), dtype), n)
                    for s in (8, 9, 10))
    # dispatch hands the rows out twice and takes a cotangent for each
    for fn, primal, cotangent, read in (
            (moe._dispatch, x, tuple(d_rows), n),
            (lambda *a: (moe._combine(*a),), out, (d_y,), TOKENS)):
        (got, got_vjp), (want, want_vjp) = (
            jax.vjp(lambda v: fn(v, plan, form), primal)
            for form in (True, False))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(f32(g)[:read], f32(w)[:read])
        got_grad, want_grad = got_vjp(cotangent)[0], want_vjp(cotangent)[0]
        read = TOKENS + n - read          # the other side's extent
        assert np.isfinite(f32(got_grad)[:read]).all()
        np.testing.assert_array_equal(f32(got_grad)[:read],
                                      f32(want_grad)[:read])
    # and the two cotangents of the rows are summed as autodiff would
    # have summed them before the gather
    summed = moe._combine(jnp.where(plan.valid[:, None],
                                    d_rows[0] + d_rows[1], 0), plan, False)
    np.testing.assert_array_equal(
        f32(moe._dispatch_bwd(True, plan, tuple(d_rows))[0]), f32(summed))


def layer_inputs(dtype, hidden, seed=0, tokens=TOKENS):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = normal(11, (tokens, hidden), dtype)
    router = jax.random.normal(keys[1], (hidden, EXPERTS)) / hidden ** 0.5
    stack = lambda k, i, o: jax.random.normal(k, (HELD, i, o)) / i ** 0.5
    return (x, router, jnp.zeros(EXPERTS), stack(keys[2], hidden, 128),
            stack(keys[3], hidden, 128), stack(keys[4], 128, hidden))


@pytest.mark.parametrize("kept,tokens", [
    (None, TOKENS),
    ((moe.CHOICE_NAME, moe.ROWS_NAME, moe.EXPERT_GATE_UP_NAME), TOKENS),
    ((moe.CHOICE_NAME,), 4 * TOKENS)],
    ids=["plain", "remat-kept", "remat-dropped"])
@dtypes
def test_the_layer_through_the_kernels_is_the_layer_through_xla(
        kernels, dtype, kept, tokens):
    """``routed_experts(kernels=True)`` against ``kernels=False``: the
    output and every gradient, plain and recomputed with the sorted rows
    and the first products' outputs kept or made again, the grouped
    products over one row tile that the four groups share and (the
    recomputed case that drops its names) over four;
    float32 to its roundings, bfloat16 to a few of its own on the
    largest entry (XLA's CPU rounds bfloat16 after every operation, the
    kernels once)."""
    dtype, hidden = DTYPES[dtype]
    args = layer_inputs(dtype, hidden, tokens=tokens)

    def loss(kernels_, *a):
        layer = lambda *a: moe.routed_experts(
            *a, first_expert=FIRST, top_k=TOP_K, kernels=kernels_)[0]
        if kept is not None:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *kept))
        y = layer(*a)
        return (y.astype(jnp.float32) ** 2).mean(), y
    before = pallas_moe._TRACES.value(kernel="gated_bwd")
    (got, want) = (jax.jit(jax.value_and_grad(
        functools.partial(loss, form), argnums=(0, 1, 3, 4, 5),
        has_aux=True))(*args) for form in (True, False))
    assert pallas_moe._TRACES.value(kernel="gated_bwd") >= before
    tolerance = 1e-5 if dtype == jnp.float32 else 2 ** -5
    pairs = [(got[0][1], want[0][1])] + list(zip(got[1], want[1]))
    for g, w in pairs:
        g, w = f32(g), f32(w)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=tolerance * np.abs(w).max())


def a_mesh(platform: str, devices: int):
    return types.SimpleNamespace(
        size=devices, devices=np.array(
            [types.SimpleNamespace(platform=platform)] * devices))


# tokens, top k, experts held, hidden, width
CELLS = {"qwen3-next": (8192, 10, 32, 2048, 512),
         "kanana": (16384, 6, 16, 2048, 768),
         "lfm2": (8192, 4, 16, 2048, 1536),
         "trinity-mini": (16384, 8, 16, 2048, 1024)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernels_run_on_one_tpu_device_at_every_fill(cell):
    """Kernels on one TPU device, whatever share of the buffer the
    routing is expected to fill (a sixteenth, an eighth, a quarter in
    the cells; all of it where every expert chosen is held), XLA's
    passes on a CPU and on a mesh of several devices: nothing else is
    asked."""
    tokens, top_k, _, hidden, width = CELLS[cell]
    assert moe.kernels_fit(*CELLS[cell], jnp.bfloat16)
    assert moe.kernels_fit(tokens, top_k, top_k, hidden, width, jnp.bfloat16)
    assert moe.on_one_tpu(a_mesh("tpu", 1))
    assert not moe.on_one_tpu(a_mesh("tpu", 4))
    assert not moe.on_one_tpu(a_mesh("cpu", 1))
    assert not moe.on_one_tpu(None)      # the tests' default backend


@pytest.mark.parametrize("shapes", [
    (64, 2, 4, 16, 24, jnp.float32),         # a row is no whole tile
    (8192, 10, 32, 2048, 512, jnp.float16),    # words do not pack it
    (8192, 10, 32, 2048, 500, jnp.bfloat16),   # a width off the lanes
    (8200, 10, 32, 2048, 512, jnp.bfloat16),   # tokens no whole blocks
    (8320, 1, 32, 2048, 512, jnp.bfloat16),    # rows no whole blocks
    (96, 2, 4, 2048, 128, jnp.bfloat16),       # nor whole grouped tiles
], ids=["narrow-rows", "float16", "ragged-width", "ragged-tokens",
        "ragged-rows", "ragged-grouped-tiles"])
def test_shapes_the_tiles_do_not_divide_take_the_xla_form(shapes):
    assert not moe.kernels_fit(*shapes)


@pytest.mark.parametrize("hidden,width,fit", [(16, 24, False),
                                               (2048, 128, True)],
                         ids=["a-tiny-models", "whole-tiles"])
def test_the_grouped_products_are_ragged_dot_where_the_kernels_do_not_fit(
        kernels, hidden, width, fit):
    """One rule, ``kernels_fit``: at a tiny model's shapes a caller on
    one TPU device traces ``ragged_dot`` and no ``hvd_moe_`` call, at
    whole tiles the three grouped kernels and no ``ragged_dot``."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    stack = lambda k, i, o: jax.random.normal(k, (HELD, i, o), jnp.float32)
    args = (jax.random.normal(keys[0], (TOKENS, hidden), jnp.bfloat16),
            jax.random.normal(keys[1], (hidden, EXPERTS)), jnp.zeros(EXPERTS),
            stack(keys[2], hidden, width), stack(keys[3], hidden, width),
            stack(keys[4], width, hidden))
    assert moe.kernels_fit(TOKENS, TOP_K, HELD, hidden, width,
                           jnp.bfloat16) == fit
    loss = lambda *a: moe.routed_experts(
        *a, first_expert=FIRST, top_k=TOP_K, kernels=True)[0].astype(
            jnp.float32).sum()
    # the transposes' names need the gradient; a tiny model's forward
    # pass says as much as its gradient would
    text = str(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 3, 4, 5)) if fit else loss)(*args))
    names = set(re.findall(r"hvd_moe_grouped_\w+", text))
    if fit:
        assert names == {"hvd_moe_" + name for name in GROUPED}
        assert "ragged_dot" not in text
    else:
        assert "hvd_moe_" not in text and "ragged_dot" in text


def test_importing_the_models_imports_no_pallas():
    """The kernels' module is imported where a sparse layer is traced,
    as the flash kernels' is: Pallas takes a second to import, and the
    cells that run no kernel (BERT's) do not pay it at start-up."""
    import os
    import subprocess
    import sys
    loaded = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu.models, horovod_tpu.training\n"
         "print(sorted(m for m in sys.modules if 'pallas' in m))"],
        capture_output=True, text=True, timeout=120, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert loaded.stdout.strip() == "[]", loaded.stdout


def traces():
    """``hvd_moe_kernel_traces`` by kernel, as a snapshot reads it."""
    read = hvd.metrics_snapshot()["gauges"].get("hvd_moe_kernel_traces", {})
    return {kernel: int(read.get("kernel=%s" % kernel, 0))
            for kernel in KERNELS + GROUPED}


def sparse_model(layers: int):
    """An LFM2 stack of one dense layer and ``layers`` sparse ones at a
    hidden size of whole tiles a row (1024 float32), recomputed."""
    cfg = lfm2.lfm2_tiny_config(
        hidden_size=1024, moe_intermediate_size=128, remat=True,
        dtype=jnp.float32,
        layer_types=(lfm2.CONV,) * (layers + 1),
        ffn_types=(lfm2.DENSE,) + (lfm2.SPARSE,) * layers)
    return lfm2.LFM2LMHeadModel(cfg)


def test_a_process_traces_each_kernel_once_for_four_layers_and_three_programs(
        kernels):
    """After ``jax.eval_shape`` of an init, the lowering of a forward
    and the lowering of a recomputed gradient of a model with FOUR
    sparse layers, every kernel's body was traced ONCE (a grouped
    product's once into the experts' width and once out of it: two
    shapes): ``init`` runs no kernel (it wants shapes), the four layers
    of a program share a jaxpr, and so do the programs.  And the
    gradient's lowered text
    holds a body at most twice whatever the depth (the forward's
    function and the copy JAX's dead-code pass makes of every jitted
    function inside a recomputed backward), each called once a layer."""
    before = traces()
    # 128 tokens, a buffer of whole grouped tiles: no other test's
    ids = jnp.zeros((2, 64), jnp.int32)
    model = sparse_model(4)
    every = KERNELS + GROUPED
    once = {**dict.fromkeys(KERNELS, 1), **dict.fromkeys(GROUPED, 2)}
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    assert traces() == before
    forward = jax.jit(lambda p, i: model.apply({"params": p}, i))
    forward.lower(params, ids)
    after_forward = traces()
    assert {k: after_forward[k] - before[k] for k in every} == {
        "pack_rows": 1, "rows_of_tokens": 1, "tokens_of_rows": 1,
        "add_rows": 0, "gated": 1, "gated_bwd": 0, "grouped_rows": 2,
        "grouped_rows_t": 0, "grouped_weights": 0}

    def loss(model):
        return lambda p, i: (model.apply({"params": p}, i).astype(
            jnp.float32) ** 2).mean()
    text = jax.jit(jax.grad(loss(model))).lower(params, ids).as_text()
    assert {k: traces()[k] - before[k] for k in every} == once

    def shallow(layers):
        model = sparse_model(layers)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                ids)["params"]
        return jax.jit(jax.grad(loss(model))).lower(params, ids).as_text()
    two = shallow(2)
    assert {k: traces()[k] - before[k] for k in every} == once
    for kernel in every:
        functions = len(re.findall(
            r"func\.func private @%s(_\d+)?\(" % kernel, text))
        assert once[kernel] <= functions <= 2 * once[kernel], (
            kernel, functions)
        assert functions == len(re.findall(
            r"func\.func private @%s(_\d+)?\(" % kernel, two))
    # rows of tokens: dispatch forward and combine backward, a layer
    calls = len(re.findall(r"call @rows_of_tokens(_\d+)?\(", text))
    assert calls == 2 * 4 == 2 * len(re.findall(
        r"call @rows_of_tokens(_\d+)?\(", two))


def test_the_kernels_put_their_blocks_on_record(kernels):
    """Set where a body is traced: a width no other test has."""
    plan, row_gate = plan_with(37)
    x = normal(1, (TOKENS, 2048), jnp.float32)
    moe._combine(moe._dispatch(x, plan, True)[0], plan, True)
    blocks = hvd.metrics_snapshot()["gauges"]["hvd_moe_kernel_block_rows"]
    assert blocks["kernel=rows_of_tokens"] == ROW_BLOCK
    assert blocks["kernel=pack_rows"] == ROW_BLOCK
    assert blocks["kernel=tokens_of_rows"] == TOKEN_BLOCK
