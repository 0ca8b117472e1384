"""Plain float32 reference of LFM2-MoE's next-token loss (Liquid AI's
``model_type`` ``lfm2_moe``, as published in the configuration file's
``source``).  ``rms(x) = x / sqrt(mean(x^2) + eps)``; no bias anywhere.

* ``h = E[ids]``; ``E`` is tied to the head: ``logits = (rms(h) *
  w_final) @ E^T``.
* Every layer: ``h = h + op(rms(h) * w_op)``, then ``h = h + ffn(rms(h)
  * w_ffn)``; ``op`` by the layer's type, ``ffn`` dense in the leading
  ``num_dense_layers`` layers and sparse in the rest.
* ``conv``: ``[B, C, x] = split(u @ W_in, 3)``; ``y = C * conv(B * x)``,
  ``conv`` causal and depthwise over ``conv_L_cache`` positions, zeros
  before the sequence; ``y @ W_out``.
* ``full_attention``: query heads over fewer key-value heads, each
  serving a run of consecutive query heads; ``q = rms(q) * w_q``, ``k =
  rms(k) * w_k`` over each head's width; rotary positions over the whole
  head, channel ``i`` paired with ``i + d / 2``, angle ``t *
  theta^(-2i / d)``; causal ``softmax(q k^T / sqrt(d)) v``; ``@ Wo``.
* Dense ``ffn``: ``(silu(x @ W1) * (x @ W3)) @ W2``.
* Sparse ``ffn``: ``s = sigmoid(x @ W_r)`` over all experts; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen; ``g_e = s_e``
  for them, divided by ``sum g + 1e-6`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum over the chosen e of g_e *
  expert_e(x)``, each expert a SwiGLU as above.  Of the experts the
  parameters hold those from ``first_expert`` on; what the others would
  add is left out.

The sparse layer has no dispatch: a ``[T, E]`` matrix of gates that is
zero off the chosen, and for each expert held its SwiGLU over ALL
tokens times its column, one expert after another.  Attention makes
the full scores of one key-value head's group at a time; the layers are
a Python loop, each recomputed in the backward pass.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref

GATE_SUM_EPS = 1e-6


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def matmul(x, kernel):
    return jnp.dot(x, kernel, precision=ref.HI)


def swiglu(x, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(x, w1)) * matmul(x, w3), w2)


def short_conv(u, p):
    """``p``: ``in_proj`` ([H, 3H]: B, C, x in that order),
    ``conv_kernel`` ([taps, H], the last tap at the position itself)
    and ``out_proj``."""
    b, c, x = jnp.split(matmul(u, p["in_proj"]["kernel"]), 3, axis=-1)
    taps, seq = p["conv_kernel"].shape[0], u.shape[1]
    padded = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + seq] * p["conv_kernel"][k]
               for k in range(taps))
    return matmul(c * conv, p["out_proj"]["kernel"])


def rotary(x, theta):
    """``x``: [b, s, heads, d].  Channels ``i`` and ``i + d / 2`` of a
    head turn by ``t * theta^(-2i / d)`` at position ``t``."""
    seq, d = x.shape[1], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * inverse
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def rotary_attention(x, p, eps, theta):
    """``p`` holds query ([H, heads, d]), key and value ([H, kv_heads,
    d]), out ([heads, d, H]) and the two norms' scales ([d]).
    Key-value head ``j`` serves query heads ``j * group .. (j + 1) *
    group - 1``."""
    def heads(name):
        return jnp.einsum("bsh,hnd->bsnd", x, p[name]["kernel"],
                          precision=ref.HI)
    q = rotary(rms_norm(heads("query"), p["query_norm"], eps), theta)
    k = rotary(rms_norm(heads("key"), p["key_norm"], eps), theta)
    v = heads("value")
    batch, seq, kv_heads, d = k.shape
    group = q.shape[2] // kv_heads
    keep = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_group(of_group):
        q_j, k_j, v_j = of_group    # [b, s, group, d], [b, s, d] twice
        scores = jnp.einsum("bqgd,bkd->bgqk", q_j, k_j,
                            precision=ref.HI) / jnp.sqrt(float(d))
        scores = jnp.where(keep, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(scores, axis=-1),
                          v_j, precision=ref.HI)
    # One key-value head's group after another, so that only one
    # group's scores are alive at a time.
    grouped = q.reshape(batch, seq, kv_heads, group, d)
    ctx = jax.lax.map(one_group, (jnp.moveaxis(grouped, 2, 0),
                                  jnp.moveaxis(k, 2, 0),
                                  jnp.moveaxis(v, 2, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(batch, seq, kv_heads * group, d)
    return jnp.einsum("bqnd,ndh->bqh", ctx, p["out"]["kernel"],
                      precision=ref.HI)


def gate_matrix(x, p, config, chosen=None):
    """``[T, E]`` gates, zero off the chosen, for tokens ``x`` [T, H],
    and what the router saw: ``own`` [T, top_k], its own choice, and
    ``gap`` [T, top_k], how far each expert taken lies under the least
    of its own choice in ``s + b`` (0 for one it chose itself).
    ``chosen`` takes the choice from another implementation; scores and
    gates are still this one's."""
    scores = jax.nn.sigmoid(matmul(x, p["router"]))
    biased = scores + p["expert_bias"]
    least, own = jax.lax.top_k(biased, config["num_experts_per_tok"])
    taken = own if chosen is None else chosen
    gap = jnp.maximum(
        least[:, -1:] - jnp.take_along_axis(biased, taken, axis=-1), 0.0)
    g = jnp.take_along_axis(scores, taken, axis=-1)
    if config["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + GATE_SUM_EPS)
    g = g * config["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return (jnp.zeros_like(scores).at[rows, taken].set(g),
            {"own": own, "gap": jax.lax.stop_gradient(gap)})


def sparse_ffn(x, p, config, chosen=None):
    """Every expert held over every token, times its column of the
    gate matrix.  ``x``: [b, s, H]."""
    flat = x.reshape(-1, x.shape[-1])
    gates, saw = gate_matrix(flat, p, config, chosen)
    held = p["gate"].shape[0]
    first = config.get("first_expert", 0)
    columns = gates[:, first:first + held].T[..., None]     # [held, T, 1]

    @jax.checkpoint
    def add_expert(total, of_expert):
        w1, w3, w2, column = of_expert
        return total + column * swiglu(flat, w1, w3, w2), None
    total, _ = jax.lax.scan(add_expert, jnp.zeros_like(flat),
                            (p["gate"], p["up"], p["down"], columns))
    return total.reshape(x.shape), saw


def layer_kinds(config):
    """``[(operator, is dense)]`` of the layers held, the published
    layers ``layers_held`` of the published ``layer_types``; the leading
    ``num_dense_layers`` of them are dense."""
    held = config.get("layers_held",
                      list(range(config["num_hidden_layers"])))
    return [(config["layer_types"][i], n < config["num_dense_layers"])
            for n, i in enumerate(held)]


def hidden_and_routing(params, batch, config, chosen=None):
    """The final hidden states, normed, the embedding, and by sparse
    layer what its router saw (``gate_matrix``).  ``chosen`` (``{layer:
    [T, top_k]}``) fixes the experts taken, layer by layer."""
    p = ref.f32(params)
    eps = config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    wte = p["word_embeddings"]["embedding"]
    h = wte[batch["input_ids"]]

    def layer(h, lp, kind, given):
        operator, dense = kind
        u = rms_norm(h, lp["operator_norm"], eps)
        if operator == "conv":
            h = h + short_conv(u, lp["conv"])
        else:
            h = h + rotary_attention(u, lp["attention"], eps, theta)
        u = rms_norm(h, lp["ffn_norm"], eps)
        if dense:
            mlp = lp["mlp"]
            return h + swiglu(u, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                              mlp["out"]["kernel"]), None
        y, saw = sparse_ffn(u, lp["moe"], config, given)
        return h + y, saw

    routing = {}
    for i, kind in enumerate(layer_kinds(config)):
        h, saw = jax.checkpoint(layer, static_argnums=(2,))(
            h, p["layer_%d" % i], kind, (chosen or {}).get(i))
        if saw is not None:
            routing[i] = saw
    return rms_norm(h, p["final_norm"], eps), wte, routing


def logits(params, batch, config):
    """``[B, S, V]`` over the rows of the embedding held."""
    h, wte, _ = hidden_and_routing(params, batch, config)
    return jnp.einsum("bsh,vh->bsv", h, wte, precision=ref.HI)


def loss_and_routing(params, batch, config: dict, chosen=None):
    """``params``: the tree of ``LFM2LMHeadModel``; ``batch``:
    ``input_ids`` of shape [B, S].  Position t predicts token t + 1."""
    ids = batch["input_ids"]
    h, wte, routing = hidden_and_routing(params, batch, config, chosen)
    all_logits = jnp.einsum("bsh,vh->bsv", h, wte, precision=ref.HI)
    return ref.cross_entropy(all_logits[:, :-1], ids[:, 1:]).mean(), routing


def loss(params, batch, config: dict, chosen=None):
    return loss_and_routing(params, batch, config, chosen)[0]
