"""Sequence-parallel attention correctness: ring and Ulysses attention
over an 8-device mesh must match unsharded softmax attention exactly
(causal and non-causal)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import build_mesh
from horovod_tpu.parallel.attention import (reference_attention,
                                            ring_attention,
                                            ulysses_attention)

B, S, H, D = 2, 32, 8, 16   # S sharded 8-way -> S_local = 4


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    return q, k, v


@pytest.fixture(scope="module")
def mesh():
    return build_mesh({"sp": 8})


def _run_sharded(fn, mesh, q, k, v, causal):
    sharded = jax.shard_map(
        lambda q, k, v: fn(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"))
    return np.asarray(jax.jit(sharded)(q, k, v))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(qkv, mesh, causal):
    q, k, v = qkv
    expected = np.asarray(reference_attention(q, k, v, causal=causal))
    got = _run_sharded(ring_attention, mesh, q, k, v, causal)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(qkv, mesh, causal):
    q, k, v = qkv
    expected = np.asarray(reference_attention(q, k, v, causal=causal))
    got = _run_sharded(ulysses_attention, mesh, q, k, v, causal)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


def test_ring_attention_single_shard_degenerate(qkv):
    """With one shard the ring reduces to plain attention."""
    from jax.sharding import Mesh
    q, k, v = qkv
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    got = _run_sharded(ring_attention, mesh1, q, k, v, False)
    expected = np.asarray(reference_attention(q, k, v))
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)
