"""Plain float32 reference of an AFMoE decoder's next-token loss
(``model_type`` ``afmoe``, as published in the configuration file's
``source``), and of the rule that moves its routers' selection bias.
``rms(x) = x / sqrt(mean(x^2) + eps)``; no bias in any projection.

* ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``); ``logits =
  (rms(h) * w_final) @ W_head`` with a head that is not the embedding.
* Every layer: ``a = h + rms_2(attn(rms_1(h)))``, then ``h = a +
  rms_4(ffn(rms_3(a)))``, four norms each with its own weight.
* ``attn(u)``: ``q = u @ Wq`` as heads, ``[k | v] = u @ Wkv`` as fewer
  heads, each serving a run of consecutive query heads, ``g = u @ Wg``;
  ``q = rms(q) * w_q``, ``k = rms(k) * w_k`` over a head's channels.  A
  ``sliding_attention`` layer turns channel ``i`` of a head with channel
  ``i + d / 2`` by ``t * theta^(-2i / d)`` at position ``t`` and lets
  query ``i`` see key ``j`` iff ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer turns nothing and masks ``0 <= i - j`` alone.
  ``o = softmax(q k^T / sqrt(d)) v * sigmoid(g)``; ``o @ Wo``.
* Dense ``ffn`` (the leading ``num_dense_layers``): ``(silu(x @ W1) * (x
  @ W3)) @ W2``.  Sparse ``ffn``: ``s = sigmoid(x @ W_r)`` over all
  experts; the ``num_experts_per_tok`` largest of ``s + b`` are chosen;
  ``g_e = s_e`` for them, divided by ``sum g + 1e-20`` (``route_norm``),
  times ``route_scale``; ``y = sum over the chosen e of g_e *
  expert_e(x) + shared(x)``, ``shared`` one SwiGLU on every token.  Of
  the routed experts the parameters hold those from ``first_expert``
  on; what the others would add is left out.
* The bias ``b`` after a step (``bias_after_update``): with ``n_e`` the
  pairs that chose expert ``e`` of ALL the router's, ``d_e =
  load_balance_coeff * sign(mean(n) - n_e)`` and ``b_e + d_e -
  mean(d)``.

Nothing is dispatched, skipped or kept.  Attention makes an explicit
``[queries, keys]`` mask from the positions, one head at a time and
within a head a block of queries at a time against ALL the keys, so
that a sequence of 16384 fits; a layer's kind reaches it as two numbers
(how many keys back a query sees, and 1 or 0 on the rotation's angle),
so that ONE compiled body walks the sparse layers whatever their kind
(a scan whose trips take their own layer's parameters; unrolled, the
check's program is four times the size).  The sparse layer is a ``[T,
E]`` matrix of gates that is zero off the chosen, and for each expert
held its SwiGLU over ALL tokens times its column.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref

GATE_SUM_EPS = 1e-20
# Queries a block of one head's scores holds against all the keys.
QUERY_BLOCK = 2048


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def matmul(x, kernel):
    return jnp.dot(x, kernel, precision=ref.HI)


def swiglu(x, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(x, w1)) * matmul(x, w3), w2)


def layer_kinds(config):
    """``[(attention kind, is dense)]`` of the layers held, the
    published layers ``layers_held`` of the published ``layer_types``;
    the leading ``num_dense_layers`` of them are dense."""
    held = config.get("layers_held",
                      list(range(config["num_hidden_layers"])))
    return [(config["layer_types"][i], n < config["num_dense_layers"])
            for n, i in enumerate(held)]


def rotate_halves(x, theta, on):
    """``x``: [b, s, heads, d].  Channel ``i`` of a head turns with
    channel ``i + d / 2`` by ``on * t * theta^(-2i / d)`` at position
    ``t``: ``on`` is 1 on a layer that has positions and 0 on one that
    has none (an angle of 0 turns nothing)."""
    seq, d = x.shape[1], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = on * jnp.arange(seq, dtype=jnp.float32)[:, None, None] * inverse
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def attention(x, p, config, reach, on):
    """``p`` holds query ([H, heads, d]), key_value ([H, kv_heads, 2
    d]), gate ([H, heads, d]), the two norms' scales ([d]) and out
    ([heads, d, H]).  ``reach``: how many keys back from its own
    (counted) a query sees; ``on``: 1 where the layer rotates."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    heads = lambda name: jnp.einsum("bsh,hnd->bsnd", x, p[name]["kernel"],
                                    precision=ref.HI)
    q, kv, gate = heads("query"), heads("key_value"), heads("gate")
    d = q.shape[-1]
    k, v = kv[..., :d], kv[..., d:]
    q = rotate_halves(rms_norm(q, p["query_norm"], eps), theta, on)
    k = rotate_halves(rms_norm(k, p["key_norm"], eps), theta, on)
    group = q.shape[2] // k.shape[2]
    seq = x.shape[1]
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError("the reference walks whole blocks of %d queries"
                         % block)
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def one_head(of_head):
        q_j, k_j, v_j = of_head                         # [b, s, d] each

        @jax.checkpoint
        def one_block(start):
            q_b = jax.lax.dynamic_slice_in_dim(q_j, start, block, axis=1)
            scores = jnp.einsum("bqd,bkd->bqk", q_b, k_j,
                                precision=ref.HI) / jnp.sqrt(float(d))
            back = (start + jnp.arange(block))[:, None] - key_at[None, :]
            keep = (back >= 0) & (back < reach)         # [block, s]
            scores = jnp.where(keep, scores, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1),
                              v_j, precision=ref.HI)
        blocks = jax.lax.map(one_block, jnp.arange(0, seq, block))
        return jnp.moveaxis(blocks, 0, 1).reshape(q_j.shape)
    # One head after another; key-value head ``n // group`` serves
    # query head ``n``.
    ctx = jax.lax.map(one_head, (
        jnp.moveaxis(q, 2, 0), jnp.repeat(jnp.moveaxis(k, 2, 0), group, 0),
        jnp.repeat(jnp.moveaxis(v, 2, 0), group, 0)))
    ctx = ctx * jnp.moveaxis(jax.nn.sigmoid(gate), 2, 0)
    return jnp.einsum("nbqd,ndh->bqh", ctx, p["out"]["kernel"],
                      precision=ref.HI)


def gate_matrix(x, p, config, chosen=None):
    """``[T, E]`` gates, zero off the chosen, for tokens ``x`` [T, H],
    and what the router saw: ``own`` [T, top_k], its own choice, and
    ``biased`` [T, E], every expert's ``s + b`` (what it chose by: how
    far under the least of its own choice another's choice lies is
    ``gap_under_own``).  ``chosen`` takes the choice from another
    implementation; scores and gates are still this one's."""
    scores = jax.nn.sigmoid(matmul(x, p["router"]))
    biased = scores + p["expert_bias"]
    _, own = jax.lax.top_k(biased, config["num_experts_per_tok"])
    taken = own if chosen is None else chosen
    g = jnp.take_along_axis(scores, taken, axis=-1)
    if config["route_norm"]:
        g = g / (g.sum(-1, keepdims=True) + GATE_SUM_EPS)
    g = g * config["route_scale"]
    rows = jnp.arange(x.shape[0])[:, None]
    return (jnp.zeros_like(scores).at[rows, taken].set(g),
            {"own": own, "biased": jax.lax.stop_gradient(biased)})


def gap_under_own(saw, taken):
    """``[T, top_k]``: how far each expert ``taken`` lies under the
    least of the router's own choice in ``s + b`` (0 for one it chose
    itself), from what ``gate_matrix`` says the router ``saw``."""
    of = lambda experts: jnp.take_along_axis(saw["biased"], experts, axis=-1)
    return jnp.maximum(of(saw["own"]).min(-1, keepdims=True) - of(taken), 0.0)


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
    eps, seq = config["rms_norm_eps"], batch["input_ids"].shape[1]
    h = p["word_embeddings"]["embedding"][batch["input_ids"]]
    if config["mup_enabled"]:
        h = h * jnp.sqrt(float(config["hidden_size"]))
    kinds = layer_kinds(config)
    # A layer's kind as the two numbers ``attention`` reads.
    reach = [config["sliding_window"] if kind == "sliding_attention"
             else seq for kind, _ in kinds]
    turns = [1.0 if kind == "sliding_attention" else 0.0
             for kind, _ in kinds]

    def layer(h, lp, dense, reach, on, given):
        a = h + rms_norm(
            attention(rms_norm(h, lp["input_norm"], eps), lp["attention"],
                      config, reach, on), lp["post_attention_norm"], eps)
        u = rms_norm(a, lp["pre_mlp_norm"], eps)
        if dense:
            mlp = lp["mlp"]
            y, saw = swiglu(u, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                            mlp["out"]["kernel"]), None
        else:
            y, saw = sparse_ffn(u, lp["moe"], config, given)
        return a + rms_norm(y, lp["post_mlp_norm"], eps), saw

    dense = [i for i, (_, is_dense) in enumerate(kinds) if is_dense]
    sparse = [i for i, (_, is_dense) in enumerate(kinds) if not is_dense]
    if dense != list(range(len(dense))):
        raise ValueError("the dense layers lead the stack")
    for i in dense:
        h, _ = jax.checkpoint(layer, static_argnums=(2,))(
            h, p["layer_%d" % i], True, reach[i], turns[i], None)

    @jax.checkpoint
    def sparse_layer(h, n, reach, on, given):
        lp = jax.lax.switch(n, [lambda i=i: p["layer_%d" % i]
                                for i in sparse])
        return layer(h, lp, False, reach, on, given)
    given = jnp.stack([chosen[i] for i in sparse]) if chosen else None
    h, saw = jax.lax.scan(
        lambda h, of_layer: sparse_layer(h, *of_layer), h,
        (jnp.arange(len(sparse)),
         jnp.asarray([reach[i] for i in sparse], jnp.int32),
         jnp.asarray([turns[i] for i in sparse], jnp.float32), given))
    routing = {i: jax.tree.map(lambda a: a[n], saw)
               for n, i in enumerate(sparse)}
    return rms_norm(h, p["final_norm"], eps), p["lm_head"], routing


def logits(params, batch, config):
    """``[B, S, V]`` over the rows of the head held."""
    h, head, _ = hidden_and_routing(params, batch, config)
    return jnp.einsum("bsh,vh->bsv", h, head, precision=ref.HI)


def loss_and_routing(params, batch, config: dict, chosen=None):
    """``params``: the tree of ``AfmoeLMHeadModel``; ``batch``:
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


def bias_after_update(bias, taken, config: dict):
    """One layer's selection bias ``[E]`` after the step whose batch
    took the experts ``taken`` ``[T, top_k]`` (of ALL ``E``): an expert
    under the mean load is raised by ``load_balance_coeff``, one over
    it lowered, and the whole recentred (``d - mean(d)`` with the
    coefficient taken out of both)."""
    loads = jnp.zeros(bias.shape, jnp.float32).at[taken.reshape(-1)].add(1.0)
    sign = jnp.sign(loads.mean() - loads)
    return bias + config["load_balance_coeff"] * (sign - sign.mean())
