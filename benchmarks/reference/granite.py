"""Plain float32 reference of Granite 4.0-H's next-token loss
(IBM's ``model_type`` ``granitemoehybrid`` with no routed expert, as
published in the configuration file's ``source``; the state-space layer is Mamba-2, Dao and Gu
2024, arXiv:2405.21060).  ``rms(x) = x / sqrt(mean(x^2) + eps)``.

* ``h = E[ids] * embedding_multiplier``; ``E`` is tied to the head.
* Every layer: ``h = h + r * mixer(rms(h) * w1)``, then ``h = h + r *
  mlp(rms(h) * w2)`` with ``r`` the ``residual_multiplier``.
* ``mlp(x) = (silu(a) * b) @ W_out``, ``[a, b] = split(x @ W_in, 2)``.
* Attention layers: query heads over fewer key-value heads, each
  serving a run of consecutive query heads; no bias, no positions;
  causal ``softmax(q k^T * attention_multiplier) v``.
* Mamba-2 layers: ``[z, xBC, dt] = x @ W_in_proj``; ``xBC = silu(conv(
  xBC) + b_conv)``, causal and depthwise; ``[x, B, C] = xBC``; ``dt =
  softplus(dt + dt_bias)``; ``a_t = exp(-exp(A_log) dt_t)``; ``S_t = a_t
  S_{t-1} + dt_t x_t (outer) B_t`` from ``S_{-1} = 0``; ``y_t = S_t C_t
  + D x_t``; ``y = rms(y * silu(z)) * w_norm`` over all channels; ``y @
  W_out_proj``.
* ``logits = (rms(h) * w_final) @ E^T / logits_scaling``.

The recurrence is a ``lax.scan`` over single positions, checkpointed in
blocks of time so that its backward pass keeps one state a block and
one block's states, not every position's; attention makes the full
scores of one key-value head's group at a time; the layers are a
Python loop, each recomputed in the backward pass.  Nothing here knows
the chunked form the program computes.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref

# Positions of the recurrence in one checkpointed block: the backward
# pass holds seq / SCAN_BLOCK block-entry states and SCAN_BLOCK states
# of the block it is in (64 + 64 of 4 MB at 2 x 4096, published widths).
SCAN_BLOCK = 64


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def matmul(x, kernel):
    return jnp.dot(x, kernel, precision=ref.HI)


def gated_mlp(x, p):
    """``gate`` and ``up`` hold ``W_in``'s two halves: ``[a, b] =
    split(x @ W_in, 2)``."""
    a, b = matmul(x, p["gate"]["kernel"]), matmul(x, p["up"]["kernel"])
    return matmul(jax.nn.silu(a) * b, p["out"]["kernel"])


def grouped_attention(x, p, scale):
    """``p`` holds query ([H, heads, d]), key and value ([H, kv_heads,
    d]) and out ([heads, d, H]).  Key-value head ``j`` serves query
    heads ``j * group .. (j + 1) * group - 1``."""
    def heads(name):
        return jnp.einsum("bsh,hnd->bsnd", x, p[name]["kernel"],
                          precision=ref.HI)
    q, k, v = heads("query"), heads("key"), heads("value")
    group = q.shape[2] // k.shape[2]
    seq = x.shape[1]
    keep = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_group(of_group):
        q_j, k_j, v_j = of_group    # [b, s, group, d], [b, s, d] twice
        scores = jnp.einsum("bqgd,bkd->bgqk", q_j, k_j,
                            precision=ref.HI) * scale
        scores = jnp.where(keep, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(scores, axis=-1),
                          v_j, precision=ref.HI)
    # One key-value head's group after another (a ``lax.map``, so that
    # only one group's scores are alive at a time).
    batch, _, kv_heads, d = k.shape
    grouped = q.reshape(batch, seq, kv_heads, group, d)
    ctx = jax.lax.map(one_group, (jnp.moveaxis(grouped, 2, 0),
                                  jnp.moveaxis(k, 2, 0),
                                  jnp.moveaxis(v, 2, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(batch, seq, kv_heads * group, d)
    return jnp.einsum("bqnd,ndh->bqh", ctx, p["out"]["kernel"],
                      precision=ref.HI)


def causal_conv(x, kernel, bias):
    """``out_t = bias + sum_k kernel[k] * x_{t - (K - 1) + k}``, zeros
    before the sequence.  ``x``: [b, s, c]; ``kernel``: [K, c]."""
    taps, seq = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + seq] * kernel[k] for k in range(taps))


def selective_scan(x, dt, a, b, c):
    """``y_t = S_t C_t`` of ``S_t = exp(a dt_t) S_{t-1} + dt_t x_t
    (outer) B_t``, one position at a time.  ``x``: [b, s, h, p]; ``dt``:
    [b, s, h]; ``a``: [h], negative; ``b``, ``c``: [b, s, n]."""
    batch, seq, heads, head_dim = x.shape

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        decay = jnp.exp(a * dt_t)[..., None, None]           # [b, h, 1, 1]
        state = decay * state + jnp.einsum(
            "bh,bhp,bn->bhpn", dt_t, x_t, b_t, precision=ref.HI)
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t,
                                 precision=ref.HI)

    @jax.checkpoint
    def block(state, of_block):
        return jax.lax.scan(position, state, of_block)

    size = min(SCAN_BLOCK, seq)
    pad = -seq % size   # padded positions have dt = 0: the state stays

    def blocks(t):      # [b, s, ...] as [blocks, size, b, ...]
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(-1, size, *t.shape[1:])
    start = jnp.zeros((batch, heads, head_dim, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, start,
                        (blocks(x), blocks(dt), blocks(b), blocks(c)))
    y = y.reshape(-1, batch, heads, head_dim)[:seq]
    return jnp.moveaxis(y, 0, 1)


def mamba2(x, p, config):
    heads, head_dim = config["mamba_n_heads"], config["mamba_d_head"]
    inner, state = heads * head_dim, config["mamba_d_state"]
    zxbcdt = matmul(x, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * state], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    xs, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    xs = xs.reshape(*xs.shape[:2], heads, head_dim)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = selective_scan(xs, dt, -jnp.exp(p["A_log"]), b, c)
    y = y + p["D"][:, None] * xs
    y = y.reshape(*y.shape[:2], inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], config["rms_norm_eps"])
    return matmul(y, p["out_proj"]["kernel"])


def logits(params, batch, config):
    """``[B, S, V]`` over the rows of the embedding held."""
    p = ref.f32(params)
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    wte = p["word_embeddings"]["embedding"]
    h = wte[batch["input_ids"]] * config["embedding_multiplier"]

    def layer(h, lp, kind):
        x = rms_norm(h, lp["mixer_norm"], eps)
        if kind == "attention":
            mixed = grouped_attention(x, lp["attention"],
                                      config["attention_multiplier"])
        else:
            mixed = mamba2(x, lp["mamba"], config)
        h = h + r * mixed
        return h + r * gated_mlp(rms_norm(h, lp["mlp_norm"], eps), lp["mlp"])

    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        h = jax.checkpoint(layer, static_argnums=(2,))(
            h, p["layer_%d" % i], kind)
    h = rms_norm(h, p["final_norm"], eps)
    return jnp.einsum("bsh,vh->bsv", h, wte,
                      precision=ref.HI) / config["logits_scaling"]


def loss(params, batch, config: dict):
    """``params``: the tree of ``GraniteLMHeadModel``; ``batch``:
    ``input_ids`` of shape [B, S].  Position t predicts token t + 1."""
    ids = batch["input_ids"]
    return ref.cross_entropy(logits(params, batch, config)[:, :-1],
                             ids[:, 1:]).mean()
