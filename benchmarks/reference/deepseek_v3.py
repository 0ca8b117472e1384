"""Plain float32 reference of a DeepSeek-V3-style decoder's next-token
loss (``model_type`` ``deepseek_v3``, as published in the configuration
file's ``source``).  ``rms(x) = x / sqrt(mean(x^2) + eps)``; no bias
anywhere.

* ``h = E[ids]``; ``logits = (rms(h) * w_final) @ W_head`` with a head
  that is not the embedding (``tie_word_embeddings`` false).
* Every layer: ``h = h + attn(rms(h) * w_1)``, then ``h = h + ffn(rms(h)
  * w_2)``; ``ffn`` dense in the leading ``first_k_dense_replace``
  layers and sparse in the rest.
* ``attn``: ``q = u @ Wq``, heads of ``[q_nope | q_rope]``; ``u @ W_kva
  = [c_kv | k_rope]``; ``[k_nope | v]`` of every head ``= (rms(c_kv) *
  w_kv) @ W_kvb``; rotary positions on ``q_rope`` of every head and on
  the one ``k_rope``, channel ``2i`` paired with ``2i + 1``, angle ``t *
  theta^(-2i / d_rope)``; head ``j``'s key is ``[k_nope_j |
  rot(k_rope)]``; causal ``softmax(q k^T / sqrt(d_nope + d_rope)) v``;
  ``@ Wo``.
* Dense ``ffn``: ``(silu(x @ W1) * (x @ W3)) @ W2``.
* Sparse ``ffn``: ``s = sigmoid(x @ W_r)`` over all experts; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen; ``g_e = s_e``
  for them, divided by ``sum g + 1e-20`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum over the chosen e of g_e *
  expert_e(x) + shared(x)``, each expert a SwiGLU as above and
  ``shared`` one SwiGLU of ``n_shared_experts`` experts' width on every
  token.  Of the routed experts the parameters hold those from
  ``first_expert`` on; what the others would add is left out.

Nothing is dispatched, expanded once or kept: the sparse layer is a
``[T, E]`` matrix of gates that is zero off the chosen, and for each
expert held its SwiGLU over ALL tokens times its column, one expert
after another; attention makes one head's full scores at a time; the
loss one sequence's logits at a time; the dense layers are a Python loop
and the sparse ones, all of one shape, a scan whose trips take their
own layer's parameters, each layer recomputed in the backward pass.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref

GATE_SUM_EPS = 1e-20


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def matmul(x, kernel):
    return jnp.dot(x, kernel, precision=ref.HI)


def swiglu(x, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(x, w1)) * matmul(x, w3), w2)


def rotary_pairs(x, theta):
    """``x``: [b, s, heads, d].  Channels ``2i`` and ``2i + 1`` of a head
    turn by ``t * theta^(-2i / d)`` at position ``t``, and stay where
    they are."""
    seq, d = x.shape[1], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * inverse
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    first, second = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([first * jnp.cos(angle) - second * jnp.sin(angle),
                        second * jnp.cos(angle) + first * jnp.sin(angle)],
                       axis=-1)
    return turned.reshape(x.shape)


def latent_attention(x, p, config):
    """``p`` holds query ([H, heads, d_nope + d_rope]), kv_down ([H,
    latent + d_rope]), kv_norm's scale ([latent]), kv_up ([latent,
    heads, d_nope + d_v]) and out ([heads, d_v, H])."""
    nope, latent = config["qk_nope_head_dim"], config["kv_lora_rank"]
    theta, eps = config["rope_theta"], config["rms_norm_eps"]
    q = jnp.einsum("bsh,hnd->bsnd", x, p["query"]["kernel"],
                   precision=ref.HI)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    down = matmul(x, p["kv_down"]["kernel"])
    c_kv, k_rope = down[..., :latent], down[..., latent:]
    up = jnp.einsum("bsc,cnd->bsnd", rms_norm(c_kv, p["kv_norm"], eps),
                    p["kv_up"]["kernel"], precision=ref.HI)
    k_nope, v = up[..., :nope], up[..., nope:]
    q_rope = rotary_pairs(q_rope, theta)
    k_rope = rotary_pairs(k_rope[:, :, None, :], theta)[:, :, 0]
    seq, d = x.shape[1], q.shape[-1]
    keep = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(of_head):
        q_nope_j, q_rope_j, k_nope_j, v_j = of_head     # [b, s, width] each
        q_j = jnp.concatenate([q_nope_j, q_rope_j], axis=-1)
        # the rotated part is the same in every head's key
        k_j = jnp.concatenate([k_nope_j, k_rope], axis=-1)
        scores = jnp.einsum("bqd,bkd->bqk", q_j, k_j,
                            precision=ref.HI) / jnp.sqrt(float(d))
        scores = jnp.where(keep, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1),
                          v_j, precision=ref.HI)
    # One head after another, so that only one head's scores are alive.
    ctx = jax.lax.map(one_head, tuple(
        jnp.moveaxis(a, 2, 0) for a in (q_nope, q_rope, k_nope, v)))
    return jnp.einsum("nbqd,ndh->bqh", ctx, p["out"]["kernel"],
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


def routed_sum(flat, p, config, chosen=None):
    """Every routed expert held over every token ``flat`` [T, H], times
    its column of the gate matrix."""
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
    return total, saw


def sparse_ffn(x, p, config, chosen=None):
    """The routed sum and the shared expert.  ``x``: [b, s, H]."""
    flat = x.reshape(-1, x.shape[-1])
    routed, saw = routed_sum(flat, p, config, chosen)
    shared = p["shared"]
    always = swiglu(flat, shared["gate"]["kernel"], shared["up"]["kernel"],
                    shared["out"]["kernel"])
    return (routed + always).reshape(x.shape), saw


def hidden_and_routing(params, batch, config, chosen=None):
    """The final hidden states, normed, the head's matrix [V, H], and by
    sparse layer what its router saw (``gate_matrix``).  ``chosen``
    (``{layer: [T, top_k]}``) fixes the experts taken, layer by layer."""
    p = ref.f32(params)
    eps = config["rms_norm_eps"]
    h = p["word_embeddings"]["embedding"][batch["input_ids"]]

    def layer(h, lp, dense, given):
        h = h + latent_attention(rms_norm(h, lp["attention_norm"], eps),
                                 lp["attention"], config)
        u = rms_norm(h, lp["ffn_norm"], eps)
        if dense:
            mlp = lp["mlp"]
            return h + swiglu(u, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                              mlp["out"]["kernel"]), None
        y, saw = sparse_ffn(u, lp["moe"], config, given)
        return h + y, saw

    dense = config["first_k_dense_replace"]
    for i in range(dense):
        h, _ = jax.checkpoint(layer, static_argnums=(2,))(
            h, p["layer_%d" % i], True, None)
    # The sparse layers have one shape, so ONE compiled body walks them
    # (unrolled, the check's program is four times the size and does
    # not fit the chip machine's compile cache beside the others): a
    # scan over their number, each trip taking its own layer's
    # parameters (a switch: stacking them would hold every layer twice).
    sparse = list(range(dense, config["num_hidden_layers"]))

    @jax.checkpoint
    def sparse_layer(h, n, given):
        lp = jax.lax.switch(n, [lambda i=i: p["layer_%d" % i]
                                for i in sparse])
        return layer(h, lp, False, given)
    given = jnp.stack([chosen[i] for i in sparse]) if chosen else None
    h, saw = jax.lax.scan(
        lambda h, of_layer: sparse_layer(h, *of_layer), h,
        (jnp.arange(len(sparse)), given))
    routing = {i: jax.tree.map(lambda a: a[n], saw)
               for n, i in enumerate(sparse)}
    return rms_norm(h, p["final_norm"], eps), p["lm_head"], routing


def logits(params, batch, config):
    """``[B, S, V]`` over the rows of the head held."""
    h, head, _ = hidden_and_routing(params, batch, config)
    return jnp.einsum("bsh,vh->bsv", h, head, precision=ref.HI)


def loss_and_routing(params, batch, config: dict, chosen=None):
    """``params``: the tree of ``DeepseekV3LMHeadModel``; ``batch``:
    ``input_ids`` of shape [B, S].  Position t predicts token t + 1."""
    ids = batch["input_ids"]
    h, head, routing = hidden_and_routing(params, batch, config, chosen)

    @jax.checkpoint
    def of_sequence(one):
        h_b, ids_b = one                 # [S, H], [S]
        all_logits = jnp.einsum("sh,vh->sv", h_b, head, precision=ref.HI)
        return ref.cross_entropy(all_logits[:-1], ids_b[1:])
    return jax.lax.map(of_sequence, (h, ids)).mean(), routing


def loss(params, batch, config: dict, chosen=None):
    return loss_and_routing(params, batch, config, chosen)[0]
