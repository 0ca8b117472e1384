"""Pieces both plain references share: float32 ``jax.numpy`` written
from the published equations, no Flax, no kernels, no bf16.

The references read the parameter tree the program's Flax models make
(so both sides see the same seeded weights) and nothing else of the
program.  Every matrix product runs at ``Precision.HIGHEST``: on a TPU a
float32 product otherwise runs in bf16 passes.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_norm(x, p, eps):
    """Ba et al. 2016: normalise the last axis, then scale and shift."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    """Hendrycks & Gimpel 2016, the tanh form (GPT-2's ``gelu_new``)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def dense(x, p):
    return jnp.dot(x, p["kernel"], precision=HI) + p["bias"]


def multi_head_attention(x, p, causal: bool):
    """Vaswani et al. 2017, section 3.2.  ``p`` holds query, key, value
    ([H, heads, d] kernels) and out ([heads, d, H])."""
    def heads(name):
        return jnp.einsum("bsh,hnd->bsnd", x, p[name]["kernel"],
                          precision=HI) + p[name]["bias"]
    q, k, v = heads("query"), heads("key"), heads("value")
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HI)
    scores = scores / math.sqrt(q.shape[-1])
    if causal:
        s = x.shape[1]
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v, precision=HI)
    return jnp.einsum("bqnd,ndh->bqh", ctx, p["out"]["kernel"],
                      precision=HI) + p["out"]["bias"]


def stack_layers(tree, num_layers: int):
    """``layer_0 .. layer_{n-1}`` as one tree with a leading layer axis,
    so that the reference scans over depth and compiles in seconds."""
    layers = [tree["layer_%d" % i] for i in range(num_layers)]
    return jax.tree.map(lambda *a: jnp.stack(a), *layers)


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


# -- the comparison that decides ``correct`` --------------------------------
#
# The system computes in bf16 (8 bits of mantissa, products accumulated
# in float32) from float32 parameters; the reference in float32 at
# HIGHEST.  Each bf16 rounding is 2^-9 = 0.2 % relative, and the error of
# a loss or of a gradient leaf after 24 layers is a random walk over
# those roundings.  The bounds below are set to about four times what
# the v5e showed on seeds 0 to 2 (PERF.md section 6, PR 22).  fp8
# inputs (3 bits of mantissa, 6 % a rounding, 32 times bf16's) or
# fp16 accumulation (which loses the small terms of a 1024-term sum)
# would exceed them severalfold.
LOSS_RTOL = 5e-3
GRAD_REL_L2 = 1e-1


def get_leaf(tree, path: str):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def with_leaves(tree, leaves: dict):
    """A copy of the nested dict ``tree`` with ``leaves`` (path ->
    array) put in place."""
    out = dict(tree)
    for path, value in leaves.items():
        head, _, rest = path.partition("/")
        if rest:
            out[head] = with_leaves(out[head], {rest: value})
        else:
            out[head] = value
    return out


def compare(system_loss, reference_loss, params, batch, leaf_names):
    """Loss and the gradient of the named leaves, system against
    reference, on the same parameters and batch.  Returns ``(ok,
    report)``; the report holds the numbers either way."""
    picked = {n: get_leaf(params, n) for n in leaf_names}

    def value_and_grad(loss):
        def of_leaves(leaves, params, batch):
            return loss(with_leaves(params, leaves), batch)
        return jax.jit(jax.value_and_grad(of_leaves))

    got_loss, got = value_and_grad(system_loss)(picked, params, batch)
    with jax.default_matmul_precision("highest"):
        want_loss, want = value_and_grad(reference_loss)(
            picked, params, batch)
    got_loss, want_loss = float(got_loss), float(want_loss)
    report = {"system_loss": got_loss, "reference_loss": want_loss,
              "loss_rel_err": abs(got_loss - want_loss) / abs(want_loss),
              "grad_rel_l2": {}}
    ok = np.isfinite(got_loss) and report["loss_rel_err"] <= LOSS_RTOL
    for name in leaf_names:
        g = np.asarray(got[name], np.float32)
        w = np.asarray(want[name], np.float32)
        err = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        report["grad_rel_l2"][name] = err
        ok = ok and np.isfinite(err) and err <= GRAD_REL_L2
    report["loss_rtol"], report["grad_rel_l2_tol"] = LOSS_RTOL, GRAD_REL_L2
    return bool(ok), report
