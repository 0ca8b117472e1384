"""Pallas flash-attention kernel correctness (interpreter mode on CPU —
the same kernel code compiles via Mosaic on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.parallel.attention import reference_attention

B, S, H, D = 2, 64, 2, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(qkv, causal):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, causal=causal,
                                     block_q=16, block_k=16,
                                     interpret=True))
    exp = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)


def test_flash_uneven_blocks(qkv):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, block_q=48, block_k=24,
                                     interpret=True))
    exp = np.asarray(reference_attention(q, k, v))
    np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)


def test_flash_gradients_match(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True,
                                        block_q=16, block_k=16,
                                        interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(reference_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


def test_flash_bf16(qkv):
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
    got = np.asarray(flash_attention(q, k, v, block_q=16, block_k=16,
                                     interpret=True).astype(jnp.float32))
    exp = np.asarray(reference_attention(q, k, v).astype(jnp.float32))
    np.testing.assert_allclose(got, exp, atol=3e-2, rtol=3e-2)


def test_bert_flash_attention_matches_einsum():
    from horovod_tpu.models.bert import (BertForMaskedLM,
                                         bert_tiny_config)
    import dataclasses
    cfg_e = bert_tiny_config(dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg_e, attention_impl="flash")
    rng = jax.random.PRNGKey(0)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg_e.vocab_size, (2, 16), dtype=np.int32))
    m_e, m_f = BertForMaskedLM(cfg_e), BertForMaskedLM(cfg_f)
    params = m_e.init(rng, ids)
    out_e = np.asarray(m_e.apply(params, ids).astype(jnp.float32))
    # The model calls the kernel compiled; off the TPU the test asks
    # Pallas for interpret mode here, by name.
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        out_f = np.asarray(m_f.apply(params, ids).astype(jnp.float32))
    np.testing.assert_allclose(out_f, out_e, atol=3e-2, rtol=3e-2)


def test_flash_compiled_is_refused_off_tpu(qkv):
    """No quiet interpret default: off the TPU the compiled kernel
    raises instead of running some other code in its place."""
    q, k, v = qkv
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, k, v)
