"""The chunked gated delta rule (``ops/gated_delta.py``) against the
recurrence itself, one position after another, in float32 at full
precision: the output and all five gradients."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import horovod_tpu as hvd
from horovod_tpu.models import qwen3_next
from horovod_tpu.ops import gated_delta, pallas_gated_delta
from horovod_tpu.ops.gated_delta import gated_delta_chunked
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.training import make_qwen3_next_train_step


def recurrence(q, k, v, g, beta):
    """``S = exp(g_t) S``; ``r = S^T k_t``; ``S += k_t (outer) (beta_t
    (v_t - r))``; ``o_t = S^T q_t``, a position at a time."""
    hi = jax.lax.Precision.HIGHEST

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read),
            precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)
    batch, _, heads, d_k = k.shape
    _, o = jax.lax.scan(
        position, jnp.zeros((batch, heads, d_k, v.shape[-1])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def inputs(seq, batch=2, heads=3, d_k=16, d_v=8, seed=0):
    """Queries and keys of norm 1 (the query scaled as the model scales
    it), decays between 0.25 and 1, write strengths between 0 and 1."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, seq, heads, d_k))) * d_k ** -.5
    k = unit(jax.random.normal(keys[1], (batch, seq, heads, d_k)))
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q, k, v, g, beta


CASES = {
    # a chunk divides the sequence; it does not; one position a chunk;
    # one chunk as long as the sequence; one longer than the sequence
    "divides": (96, 32), "ragged": (100, 32), "chunk-1": (24, 1),
    "one-chunk": (96, 96), "chunk-over-seq": (40, 64), "chunk-64": (192, 64)}


@pytest.mark.parametrize("seq,chunk", CASES.values(), ids=CASES.keys())
def test_chunked_form_equals_the_recurrence(seq, chunk):
    args = inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: gated_delta_chunked(*a, chunk))(*args)
        want = jax.jit(recurrence)(*args)
    assert got.shape == want.shape == (2, seq, 3, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("seq,chunk", CASES.values(), ids=CASES.keys())
def test_all_five_gradients_equal_the_recurrences(seq, chunk):
    """Through the chunks' triangular solve, the masked exponentials
    and the walk over the chunks."""
    args = inputs(seq, seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, 3, 8))
    loss = lambda f: lambda *a: jnp.sum(f(*a) * weight)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            loss(lambda *a: gated_delta_chunked(*a, chunk)),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(loss(recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4,
            atol=2e-5 * float(np.abs(b).max()), err_msg=name)


def test_no_write_strength_only_decays_and_the_state_stays_empty():
    """``beta`` = 0: nothing is ever written, so every output is 0,
    whatever the decay."""
    q, k, v, g, _ = inputs(96)
    got = gated_delta_chunked(q, k, v, g, jnp.zeros_like(g), 32)
    assert float(jnp.abs(got).max()) == 0.0


def test_no_decay_and_full_strength_is_the_plain_delta_rule():
    """``g`` = 0 and ``beta`` = 1: ``S += k (v - S^T k)^T``; after a
    position its own key reads back its own value (keys of norm 1)."""
    q, k, v, g, beta = inputs(96)
    zeros, ones = jnp.zeros_like(g), jnp.ones_like(beta)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_chunked(q, k, v, zeros, ones, 32)
        want = recurrence(q, k, v, zeros, ones)
        # the query IS the key: the output is the value just written
        back = gated_delta_chunked(k, k, v, zeros, ones, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(back), np.asarray(v), rtol=1e-4,
                               atol=1e-5)


def test_bfloat16_operands_keep_the_small_matrices_in_float32():
    """In bfloat16 the wide products round their operands; ``A``, ``T``,
    the decays and the carried state do not, so the result stays within
    bfloat16's rounding of the float32 one."""
    args = inputs(128, d_k=32, d_v=32)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    got = jax.jit(lambda *a: gated_delta_chunked(*a, 64))(q, k, v, *args[3:])
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = recurrence(*(t.astype(jnp.float32) for t in (q, k, v)),
                          *args[3:])
    err = np.linalg.norm(np.asarray(got, np.float32) - np.asarray(want)) \
        / np.linalg.norm(np.asarray(want))
    assert err < 2e-2, err


@pytest.mark.parametrize("alike", [0.0, 0.9, 0.99],
                         ids=["random", "alike", "nearly-one"])
def test_keys_that_are_alike_and_barely_decay_stay_exact(alike):
    """A chunk of 64 whose keys share a direction, with ``beta`` near 1
    and next to no decay: ``A``'s entries are near 1 and its powers
    grow past float32's reach, which is why the chunk's system is
    solved row by row and its inverse is not summed from powers."""
    q, k, v, g, beta = inputs(128, d_k=32, d_v=32, seed=3)
    common = jnp.ones_like(k) / np.sqrt(k.shape[-1])
    mix = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    k = mix((1 - alike) * k + alike * common)
    g, beta = 1e-3 * g, 0.95 + 0.05 * beta
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: gated_delta_chunked(*a, 64))(q, k, v, g,
                                                              beta)
        want = jax.jit(recurrence)(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3,
                               atol=1e-4 * float(np.abs(want).max()))


@pytest.mark.parametrize("heads,d_k,d_v", [(1, 8, 8), (2, 8, 24), (5, 32, 16)])
def test_heads_and_widths_of_any_size(heads, d_k, d_v):
    """Keys and values of different widths, any number of heads."""
    args = inputs(80, heads=heads, d_k=d_k, d_v=d_v, seed=2)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: gated_delta_chunked(*a, 16))(*args)
        want = jax.jit(recurrence)(*args)
    assert got.shape == (2, 80, heads, d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_scan_bytes_counts_the_arrays_by_hand():
    """One layer at [2, 8192] with 32 heads of 128 | 128 in chunks of
    64, bfloat16: four squares of 64 a chunk and head (the decay, ``A``
    and the queries' scores in float32, the weights in bfloat16), w, u
    and v_new of every position, a state a chunk."""
    squares = 2 * 32 * 128 * 64 * 64
    positions = 2 * 8192 * 32
    states = 2 * 128 * 32 * 128 * 128
    assert gated_delta.scan_bytes(2, 8192, 32, 128, 128, 64, 2) == \
        squares * 14 + positions * (128 * 2 + 2 * 128 * 4) + states * 4
    # a ragged sequence counts its padded chunk
    assert gated_delta.scan_bytes(1, 100, 1, 4, 4, 32, 4) == \
        gated_delta.scan_bytes(1, 128, 1, 4, 4, 32, 4)
    # where the kernels make ``A`` in VMEM it is not counted: the
    # benchmark's cell, 1 x 8192, reads 0.71875 GiB for 0.78125
    in_cell = lambda **kw: gated_delta.scan_bytes(1, 8192, 32, 128, 128, 64,
                                                  2, **kw) / 2 ** 30
    assert (in_cell(), in_cell(in_vmem=True)) == (0.78125, 0.71875)


def cast_twice(t, dtype):
    """``t`` rounded to ``dtype`` as the operator's body rounds it."""
    return t.astype(dtype).astype(jnp.float32)


def straight_through(t, dtype):
    """``t`` rounded to ``dtype``, the derivative the identity."""
    return t + jax.lax.stop_gradient(cast_twice(t, dtype) - t)


def plain_walk(w, u, k_to_end, chunk_decay, rounded=cast_twice):
    """The walk over the chunks as one ``lax.scan`` that autodiff
    differentiates: the body ``_walk``'s forward rule runs, written
    out again here for its written-out backward to be held to."""
    dtype = w.dtype

    def step(carried, of_chunk):
        w_c, u_c, k_c, decay = of_chunk
        v_new = u_c - jnp.einsum("blhk,bhkv->blhv", rounded(w_c, dtype),
                                 rounded(carried, dtype))
        left = carried * decay[..., None, None] + jnp.einsum(
            "blhk,blhv->bhkv", rounded(k_c, dtype), rounded(v_new, dtype))
        return left, (carried, v_new)
    _, batch, _, heads, d_k = w.shape
    _, outs = jax.lax.scan(
        step, jnp.zeros((batch, heads, d_k, u.shape[-1]), jnp.float32),
        (w, u, k_to_end, chunk_decay))
    return outs


def walk_operands(seq, chunk, dtype, batch=2, heads=3, d_k=16, d_v=8):
    """Operands as the operator hands them to its walk, the chunks
    first: corrections and keys of a size that keeps the state near 1,
    decays between 0.25 and 1, and the padded positions of a last chunk
    that ``chunk`` does not fill zero in ``w``, ``u`` and ``k_to_end``.
    With them a cotangent for each of the walk's two outputs."""
    count, length = gated_delta.chunks_of(seq, chunk)
    keys = jax.random.split(jax.random.PRNGKey(seq + chunk), 6)
    real = (jnp.arange(count * length) < seq).reshape(
        count, 1, length, 1, 1)
    rows = lambda key, width: real * jax.random.normal(
        key, (count, batch, length, heads, width)) / length ** .5
    w, u, k_to_end = rows(keys[0], d_k), rows(keys[1], d_v), rows(keys[2],
                                                                 d_k)
    decay = jax.random.uniform(keys[3], (count, batch, heads), minval=.25)
    cotangents = (
        jax.random.normal(keys[4], (count, batch, heads, d_k, d_v)),
        jax.random.normal(keys[5], u.shape))
    return (w.astype(dtype), u, k_to_end.astype(dtype), decay), cotangents


def walk_gradients(walk, operands, cotangents):
    return jax.jit(lambda: jax.vjp(walk, *operands)[1](cotangents))()


WALKS = {**CASES, "two-chunks-ragged": (33, 32)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq,chunk", WALKS.values(), ids=WALKS.keys())
def test_written_out_backward_equals_autodiff_of_the_plain_scan(seq, chunk,
                                                                dtype):
    """All four operands' gradients, with a cotangent on the entered
    states AND on ``v_new``: to 1e-5 of the gradient's largest entry,
    and one rounding where a gradient comes back in bfloat16.  The
    roundings of the plain scan's operands are straight-through here,
    as the written-out rule takes them; in float32 they are nothing."""
    operands, cotangents = walk_operands(seq, chunk, dtype)
    outs, want_outs = (jax.jit(walk)(*operands)
                       for walk in (gated_delta._walk, plain_walk))
    for a, b in zip(outs, want_outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got = walk_gradients(gated_delta._walk, operands, cotangents)
    want = walk_gradients(
        functools.partial(plain_walk, rounded=straight_through), operands,
        cotangents)
    for name, a, b, operand in zip("w u k_to_end chunk_decay".split(), got,
                                   want, operands):
        assert a.dtype == b.dtype == operand.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # The last chunk leaves its state to nobody and the first is
        # entered with zeros: under three chunks some gradients are 0.
        assert np.abs(b).max() > 0 or len(operand) < 3, name
        # a step of bfloat16 is at most 2^-7 of the number
        rounding = 2.0 ** -7 if operand.dtype == jnp.bfloat16 else 0.0
        np.testing.assert_allclose(
            a, b, rtol=rounding, atol=1e-5 * float(np.abs(b).max()),
            err_msg=name)


@pytest.mark.parametrize("seq,chunk", WALKS.values(), ids=WALKS.keys())
def test_written_out_backward_rounds_no_cotangent(seq, chunk):
    """Autodiff of ``t.astype(bfloat16).astype(float32)`` rounds the
    COTANGENT to bfloat16 on its way back, once a product and trip; the
    written-out rule does not.  So against autodiff of the body as it
    is written the bfloat16 gradients lie within one rounding a trip
    of the largest entry (in float32 the two bodies are one)."""
    operands, cotangents = walk_operands(seq, chunk, jnp.bfloat16)
    got = walk_gradients(gated_delta._walk, operands, cotangents)
    want = walk_gradients(plain_walk, operands, cotangents)
    trips = len(operands[0])
    for name, a, b in zip("w u k_to_end chunk_decay".split(), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(
            a, b, rtol=0, err_msg=name,
            atol=(1e-5 + 2.0 ** -8 * trips) * float(np.abs(b).max()))


def _scans_in_the_gradient(*kept):
    """Scans in the jaxpr of the gradient of one operator call that is
    recomputed but for the names ``kept``."""
    loss = lambda *a: jnp.sum(gated_delta_chunked(*a, 32))
    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    gradient = jax.grad(jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2, 3, 4))
    return str(jax.make_jaxpr(gradient)(*inputs(96))).count(" scan[")


def test_kept_states_leave_no_walk_to_recompute():
    """With the walk's outputs kept across ``remat`` its backward reads
    them as they are: a forward walk and a backward walk.  Dropped,
    the forward walk is made once more, and nothing else."""
    assert _scans_in_the_gradient(gated_delta.WY_NAME,
                                  gated_delta.STATES_NAME) == 2
    assert _scans_in_the_gradient(gated_delta.WY_NAME) == 3
    assert _scans_in_the_gradient() == 3


# -- the within-chunk system as Pallas kernels (ops/pallas_gated_delta.py) --

WIDE = dict(heads=2, d_k=128, d_v=128)   # the least the kernels' tiles divide


@pytest.fixture
def interpreted(monkeypatch):
    """The two kernels' bodies in the interpreter that is pure JAX."""
    for name in ("wy_fwd", "wy_bwd"):
        monkeypatch.setattr(pallas_gated_delta, name, functools.partial(
            getattr(pallas_gated_delta, name), interpret=True))


def systems(seq, dtype=jnp.float32, chunk=64, seed=0, **kw):
    """``_wy_by_xla``'s operands as ``gated_delta_chunked`` makes them
    of ``inputs(seq)``: chunked, the last chunk padded with ``g`` = 0
    and ``beta`` = 0."""
    _, k, v, g, beta = inputs(seq, batch=1, seed=seed, **{**WIDE, **kw})
    count, length = gated_delta.chunks_of(seq, chunk)

    def chunked(t):
        t = jnp.pad(t, ((0, 0), (0, count * length - seq))
                    + ((0, 0),) * (t.ndim - 2))
        return t.reshape(1, count, length, *t.shape[2:])
    k, v, g, beta = (chunked(t) for t in (k, v, g, beta))
    return k.astype(dtype), v.astype(dtype), beta, jnp.cumsum(
        jnp.transpose(g, (0, 3, 1, 2)), axis=-1)


def by_xla(k, v, beta, cum):
    """``_wy_by_xla`` from the running sum, as the operator hands it
    the decays."""
    lower = jnp.tril(jnp.ones((k.shape[2],) * 2, bool))
    between = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    to_here = jnp.transpose(jnp.exp(cum), (0, 2, 3, 1))[..., None]
    return gated_delta._wy_by_xla(k, v, beta, between, to_here)


@pytest.mark.parametrize("seq,dtype", [
    (150, jnp.float32), (64, jnp.float32), (512, jnp.float32),
    (150, jnp.bfloat16)],
    ids=["padded-last-chunk", "one-chunk", "several", "bfloat16"])
def test_the_kernels_solve_what_xla_solves(interpreted, seq, dtype):
    """``w`` and ``u`` of every (head, chunk) from ``hvd_gdn_wy_fwd``
    against the triangular solve's: to 1e-5 of the largest entry in
    float32, to one step of bfloat16 where ``w`` is rounded to it."""
    operands = systems(seq, dtype)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(by_xla)(*operands)
    got = jax.jit(gated_delta._wy_in_vmem)(*operands)
    for name, g, w in zip("wu", got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        step = 2.0 ** -8 if (name, dtype) == ("w", jnp.bfloat16) else 1e-5
        np.testing.assert_allclose(g, w, rtol=0, err_msg=name,
                                   atol=step * float(np.abs(w).max()))


@pytest.mark.parametrize("seq", [100, 256], ids=["padded", "several"])
def test_gradients_through_the_kernels_equal_autodiffs(interpreted, seq):
    """The operator's output and all five gradients, the chunks'
    systems through the two kernels against XLA's solve differentiated
    by autodiff, cotangents on the whole output."""
    args = inputs(seq, batch=2, seed=3, **WIDE)
    weights = jax.random.normal(jax.random.PRNGKey(9),
                                (2, seq, 2, WIDE["d_v"]))

    def gradients(kernels):
        loss = lambda *a: jnp.sum(weights * gated_delta_chunked(
            *a, 64, kernels=kernels))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(
                *args)
    (got_loss, got), (want_loss, want) = gradients(True), gradients(False)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, err_msg=name,
                                   atol=2e-5 * float(np.abs(w).max()))


def test_the_blocked_solve_where_a_chunks_keys_are_alike(interpreted):
    """The solve's worst case: every key of a chunk the same, ``beta``
    0.999 and hardly any decay, so ``A`` is all but ones under the
    diagonal.  Against a float64 solve the kernel's error is no more
    than four times ``solve_triangular``'s own (the product ``(I - A)(I
    + A^2)...`` loses four digits here)."""
    k, v, beta, cum = systems(128, seed=5)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    beta = jnp.full_like(beta, 0.999)
    cum = jnp.cumsum(jnp.full_like(cum, -1e-4), axis=-1)
    with jax.default_matmul_precision("highest"):
        _, by_solver = jax.jit(by_xla)(k, v, beta, cum)
    _, by_kernel = jax.jit(gated_delta._wy_in_vmem)(k, v, beta, cum)
    k64, v64, beta64, cum64 = (np.asarray(t, np.float64)
                               for t in (k, v, beta, cum))
    worst = {"kernel": 0.0, "solver": 0.0}
    for c in range(k.shape[1]):
        for h in range(k.shape[3]):
            keys, values = k64[0, c, :, h], v64[0, c, :, h]
            decay = np.exp(np.tril(cum64[0, h, c][:, None]
                                   - cum64[0, h, c][None, :]))
            a = np.tril(beta64[0, c, :, h, None] * (keys @ keys.T) * decay,
                        -1)
            exact = np.linalg.solve(np.eye(len(a)) + a,
                                    values * beta64[0, c, :, h, None])
            for name, got in (("kernel", by_kernel), ("solver", by_solver)):
                error = np.abs(np.asarray(got, np.float64)[0, c, :, h]
                               - exact).max() / np.abs(exact).max()
                worst[name] = max(worst[name], error)
    assert worst["kernel"] <= 4 * worst["solver"] + 1e-7, worst
    assert worst["kernel"] < 1e-5, worst


def test_fits_reads_the_tiles():
    fits = pallas_gated_delta.fits
    assert fits(64, 128, 128, jnp.bfloat16) and fits(64, 128, 256, "float32")
    assert fits(16, 128, 128, jnp.bfloat16) and fits(128, 256, 128, "float32")
    # the tiny models' heads and chunks; a chunk that is no power of two,
    # one longer than a tile of lanes, one shorter than a diagonal block
    assert not fits(32, 16, 16, jnp.float32)
    assert not fits(64, 128, 64, jnp.bfloat16)
    assert not fits(96, 128, 128, jnp.bfloat16)
    assert not fits(256, 128, 128, jnp.bfloat16)
    assert not fits(8, 128, 128, jnp.bfloat16)
    assert not fits(64, 128, 128, jnp.float16)


def _kernel_calls(jaxpr) -> dict:
    """Equations of ``jaxpr`` and of every jaxpr under it, each where it
    stands (the printed form writes a repeated sub-jaxpr once)."""
    calls = {"fwd": 0, "bwd": 0, "solves": 0, "pallas": 0}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls["pallas"] += 1
                name = eqn.params["name"] or ""
                calls["fwd"] += name == "hvd_gdn_wy_fwd"
                calls["bwd"] += name == "hvd_gdn_wy_bwd"
                continue
            calls["solves"] += eqn.primitive.name == "triangular_solve"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return calls


@pytest.mark.parametrize("kept,fwd", [
    ((gated_delta.WY_NAME, gated_delta.STATES_NAME), 1),
    ((gated_delta.STATES_NAME,), 2), ((), 2)],
    ids=["wy-kept", "wy-dropped", "nothing-kept"])
def test_kept_wy_leaves_no_system_to_solve_again(kept, fwd):
    """A recomputed operator that kept ``w`` and ``u`` calls the forward
    kernel once; one that dropped them makes them again; the backward
    kernel once either way, and no triangular solve."""
    loss = lambda *a: jnp.sum(gated_delta_chunked(*a, 64, kernels=True))
    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    gradient = jax.grad(jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2, 3, 4))
    calls = _kernel_calls(jax.make_jaxpr(gradient)(*inputs(128, **WIDE)))
    assert calls == {"fwd": fwd, "bwd": 1, "solves": 0, "pallas": fwd + 1}


def _wide_step(monkeypatch, on_one_tpu, **config):
    """The tiny Qwen3-Next step with ``remat``, its delta rule at widths
    the kernels' tiles divide unless ``config`` says otherwise, traced
    where the devices are (or are taken to be) one TPU."""
    monkeypatch.setattr(moe, "on_one_tpu", lambda mesh: on_one_tpu)
    widths = dict(linear_num_key_heads=1, linear_num_value_heads=2,
                  linear_key_head_dim=128, linear_value_head_dim=128,
                  linear_chunk_size=64)
    cfg = qwen3_next.qwen3_next_tiny_config(
        dtype=jnp.float32, remat=True, **{**widths, **config})
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    init_fn, step_fn, _ = make_qwen3_next_train_step(cfg, mesh)
    ids = jax.ShapeDtypeStruct((2, 96), jnp.int32)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), ids)
    return cfg, step_fn.trace(*state, ids).jaxpr


def test_the_rule_reads_the_devices_and_the_tiles(monkeypatch):
    """Which inputs take the kernels is read from the input: on one TPU
    device at widths the tiles divide two calls a delta-rule layer (the
    layers' recomputed pass keeps ``w`` and ``u``) and no triangular
    solve; off it, or at the tiny model's own widths, XLA's solve and
    neither kernel.  One trace a kernel serves the three layers and
    their recomputed pass (``hvd_gdn_kernel_traces``)."""
    traces = gated_delta.KERNEL_TRACES
    was = {k: traces.value(kernel=k) for k in ("wy_fwd", "wy_bwd")}
    cfg, jaxpr = _wide_step(monkeypatch, True)
    layers = cfg.layer_types.count(qwen3_next.LINEAR)
    calls = _kernel_calls(jaxpr)
    assert (calls["fwd"], calls["bwd"], calls["solves"]) == (layers, layers,
                                                             0)
    # a shape the process has not traced yet: one trace each, here
    assert {k: traces.value(kernel=k) - was[k] for k in was} == {
        "wy_fwd": 1, "wy_bwd": 1}
    gauges = hvd.metrics_snapshot()["gauges"]
    assert gauges["hvd_gdn_kernel_traces"] == {
        "kernel=" + k: traces.value(kernel=k) for k in was}
    # and the step's record of the operator's arrays leaves ``A`` out
    assert gauges["hvd_gdn_scan_bytes"] == gated_delta.scan_bytes(
        2, 96, 2, 128, 128, 64, 4, in_vmem=True)
    for on_one_tpu, config in ((False, {}), (True, dict(
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_chunk_size=32))):
        _, jaxpr = _wide_step(monkeypatch, on_one_tpu, **config)
        calls = _kernel_calls(jaxpr)
        assert (calls["fwd"], calls["bwd"]) == (0, 0)
        # two a layer in the forward pass's jaxpr and its recomputed
        # one, and the solve's own transpose
        assert calls["solves"] >= 2 * layers
        assert hvd.metrics_snapshot()["gauges"]["hvd_gdn_scan_bytes"] == \
            gated_delta.scan_bytes(2, 96, 2, *((16, 16, 32) if config else
                                               (128, 128, 64)), 4)
    assert {k: traces.value(kernel=k) - was[k] for k in was} == {
        "wy_fwd": 1, "wy_bwd": 1}
