"""Pipeline (pp) and expert (ep) parallelism correctness on the
8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import build_mesh
from horovod_tpu.parallel.moe import moe_ffn, top1_dispatch
from horovod_tpu.parallel.pipeline import pipeline_apply


def test_pipeline_matches_sequential():
    """4-stage pipeline of y = x @ W_i + b_i must equal applying the
    stages in order."""
    mesh = build_mesh({"pp": 4, "dp": 2})
    S, M, B, D = 4, 6, 2, 8
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(S, D).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(M, B, D).astype(np.float32))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W[0] + b[0])

    run = jax.jit(jax.shard_map(
        lambda W, b, xm: pipeline_apply(stage, (W, b), xm,
                                        axis_name="pp"),
        mesh=mesh,
        in_specs=(P("pp"), P("pp"), P(None, None)),
        out_specs=P(None, None)))
    got = np.asarray(run(Ws, bs, x))

    expected = x
    for i in range(S):
        expected = jnp.tanh(expected @ Ws[i] + bs[i])
    np.testing.assert_allclose(got, np.asarray(expected), atol=1e-5,
                               rtol=1e-5)


def test_pipeline_is_differentiable():
    mesh = build_mesh({"pp": 4, "dp": 2})
    S, M, B, D = 4, 4, 2, 4
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
    bs = jnp.zeros((S, D), jnp.float32)
    x = jnp.asarray(rng.randn(M, B, D).astype(np.float32))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W[0] + b[0])

    def loss_sharded(W, b, xm):
        out = pipeline_apply(stage, (W, b), xm, axis_name="pp")
        return jnp.mean(out ** 2)

    f = jax.jit(jax.shard_map(
        lambda W, b, xm: jax.grad(loss_sharded)(W, b, xm),
        mesh=mesh, in_specs=(P("pp"), P("pp"), P(None, None)),
        out_specs=P("pp")))
    gW = np.asarray(f(Ws, bs, x))

    def loss_seq(Ws):
        h = x
        for i in range(S):
            h = jnp.tanh(h @ Ws[i] + bs[i])
        return jnp.mean(h ** 2)

    gW_ref = np.asarray(jax.grad(loss_seq)(Ws))
    np.testing.assert_allclose(gW, gW_ref, atol=1e-5, rtol=1e-4)


def test_top1_dispatch_capacity():
    logits = jnp.asarray([[5.0, 0.0], [4.0, 0.0], [3.0, 0.0],
                          [0.0, 5.0]])
    dispatch, combine, aux = top1_dispatch(logits, capacity=2)
    # Tokens 0,1 fit expert 0; token 2 overflows (dropped); token 3 in
    # expert 1 slot 0.
    assert dispatch[0, 0, 0] == 1 and dispatch[1, 0, 1] == 1
    assert dispatch[2].sum() == 0
    assert dispatch[3, 1, 0] == 1
    assert float(aux) > 0


@pytest.mark.parametrize("E", [2, 8])
def test_moe_aux_loss_switch_oracle(E):
    """aux must equal the Switch Transformer eq. 4 value
    E * sum_i f_i * P_i (f_i = fraction of tokens argmax-routed to
    expert i, P_i = mean router probability) — NOT E x that value."""
    T = 64
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    _, _, aux = top1_dispatch(logits, capacity=T)

    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    assign = probs.argmax(axis=-1)
    f = np.array([(assign == e).mean() for e in range(E)])
    P = probs.mean(axis=0)
    oracle = E * float((f * P).sum())
    np.testing.assert_allclose(float(aux), oracle, rtol=1e-5)


@pytest.mark.parametrize("E", [2, 8])
def test_moe_aux_loss_balanced_is_one(E):
    """Perfectly balanced, confident routing gives aux ~= 1.0 for any
    expert count, so literature alpha values transfer across E."""
    T = 8 * E
    assign = np.arange(T) % E
    logits = jnp.asarray(
        (np.eye(E)[assign] * 50.0).astype(np.float32))
    _, _, aux = top1_dispatch(logits, capacity=T)
    np.testing.assert_allclose(float(aux), 1.0, atol=1e-3)


def test_moe_matches_per_token_expert():
    """Expert-parallel MoE must equal routing each token through its
    argmax expert locally (capacity ample, identical tokens per rank)."""
    mesh = build_mesh({"ep": 8})
    T, D, E = 16, 4, 8
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8 * T, D).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(D, E).astype(np.float32))
    expert_W = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.5)

    def expert_fn(W, h):
        return jnp.tanh(h @ W[0])

    run = jax.jit(jax.shard_map(
        lambda x, gw, W: moe_ffn(x, gw, expert_fn, W,
                                 axis_name="ep",
                                 capacity_factor=8.0),
        mesh=mesh, in_specs=(P("ep"), P(), P("ep")),
        out_specs=(P("ep"), P())))
    got, aux = run(x, gate_w, expert_W)
    got = np.asarray(got)

    probs = jax.nn.softmax(x @ gate_w, axis=-1)
    expert = np.asarray(jnp.argmax(probs, axis=-1))
    gate = np.asarray(jnp.max(probs, axis=-1))
    expected = np.stack([
        gate[t] * np.tanh(np.asarray(x[t]) @ np.asarray(
            expert_W[expert[t]]))
        for t in range(x.shape[0])])
    np.testing.assert_allclose(got, expected, atol=1e-5, rtol=1e-4)
