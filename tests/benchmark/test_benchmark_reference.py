"""Each plain reference against the program's Flax model at a tiny size
in float32, and the comparison that decides ``correct`` against
compute of too low a precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import spec, tiny_config
from benchmarks.reference import common as reference


def _case(family_name, dtype, seed=0, batch=2, seq=32):
    config = dict(tiny_config(family_name), compute_dtype=dtype)
    family = spec.load_family(config)
    data = family.host_batch(config, batch, seq, np.random.default_rng(seed))
    params = family.init_params(config, jax.random.PRNGKey(seed), data)
    return config, family, params, data


@pytest.mark.parametrize("family_name", ["bert", "gpt"])
def test_reference_equals_the_flax_model_in_float32(family_name):
    config, family, params, data = _case(family_name, "float32")
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(family.system_loss(config))(
            params, data)
        want, want_g = jax.value_and_grad(family.reference_loss(config))(
            params, data)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got, flat_want = jax.tree.leaves(got_g), jax.tree.leaves(want_g)
    assert len(flat_got) == len(flat_want) > 30
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6 * float(np.abs(w).max() + 1))


@pytest.mark.parametrize("family_name", ["bert", "gpt"])
def test_comparison_passes_bf16_and_fails_fp8(family_name):
    config, family, params, data = _case(family_name, "bfloat16", seed=1)
    leaves = config["check_leaves"]
    system, ref = family.system_loss(config), family.reference_loss(config)
    ok, report = reference.compare(system, ref, params, data, leaves)
    assert ok, report
    assert set(report["grad_rel_l2"]) == set(leaves)

    def fp8(loss):
        # The same system with its weights rounded to 3 bits of
        # mantissa before use: what fp8 matmul inputs would do.
        def rounded(p, batch):
            p = jax.tree.map(
                lambda a: a + jax.lax.stop_gradient(
                    a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a), p)
            return loss(p, batch)
        return rounded
    ok, report = reference.compare(fp8(system), ref, params, data, leaves)
    assert not ok, report


def test_with_leaves_replaces_only_the_named_leaf():
    tree = {"a": {"b": 1, "c": 2}, "d": 3}
    out = reference.with_leaves(tree, {"a/b": 10})
    assert out == {"a": {"b": 10, "c": 2}, "d": 3}
    assert tree["a"]["b"] == 1 and out["a"] is not tree["a"]
    assert reference.get_leaf(out, "a/b") == 10
