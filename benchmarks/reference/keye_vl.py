"""Plain float32 reference of the next-token loss and the indexer's
alignment loss of Keye-VL-2.0's language model (``model_type``
``KeyeVL2``, as published in the configuration file's ``source``; its
``sa_config`` is DeepSeek-Sparse-Attention's indexer, arXiv
DeepSeek-V3.2-Exp report, eq. 1).  ``rms(x) = x / sqrt(mean(x^2) +
eps)``; ``sg`` is ``stop_gradient``; no bias in any projection.

* ``h = E[ids]``; ``logits = (rms(h) * w_final) @ W_head`` with a head
  that is not the embedding.
* Every layer, with ``u = rms(h) * w_1``: ``a = h + attn(u)``, then ``h
  = a + ffn(rms(a) * w_2)``.
* ``attn(u)``: ``q = u @ Wq`` as heads, ``k = u @ Wk`` and ``v = u @
  Wv`` as fewer heads, each serving a run of consecutive query heads;
  ``q = rms(q) * w_q``, ``k = rms(k) * w_k`` over a head's channels.
  Channel ``i`` of a head turns with channel ``i + d / 2`` by ``pos *
  theta^(-2i / d)``, where ``pos`` is the token's place in the position
  stream that frequency pair ``i`` reads: stream 0 under
  ``mrope_section[0]``, stream 1 for the next ``mrope_section[1]``,
  stream 2 for the rest.  Head by head, ``o_t = sum over s in S_t of
  softmax_s(q_t . k_s / sqrt(d)) v_s``; ``o @ Wo``.
* The indexer: ``q_I[t, j] = sg(u_t) @ W_Iq[j]`` for
  ``indexer_num_heads`` heads of ``indexer_head_dim``; ``k_I[s] =
  LayerNorm(sg(u_s) @ W_Ik)``, one head; both turned as above over
  their whole width by stream 0; ``w[t] = sg(u_t) @ W_Iw / sqrt(heads x
  dim)``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``.  ``S_t``
  = the keys of the ``topk`` largest ``I[t, s]`` with ``s <= t`` as
  ``jax.lax.top_k`` gives them (ties: the lower position), every causal
  key where ``t < topk``.
* ``ffn``: ``p = softmax(x @ W_r)`` over all experts; the
  ``num_experts_per_tok`` largest chosen; ``g = p / sum of the chosen
  p`` (``norm_topk_prob``); ``y = sum over the chosen e of g_e *
  SwiGLU_e(x)``.  Of the routed experts the parameters hold those from
  ``first_expert`` on; what the others would add is left out.
* ``L = L_lm + sum over the layers of L_I``: ``L_lm`` the next-token
  cross-entropy; ``L_I = mean_t KL(pbar[t, S_t] || softmax(I[t,
  S_t]))`` with ``pbar[t, s] = sg(mean_h p_h[t, s])`` divided by its sum
  over ``S_t``.  The gradient is ``jax.grad`` of that, through the two
  ``sg`` as written.

Nothing is packed, tiled, dispatched or kept.  Attention makes a block
of ``QUERY_BLOCK`` queries' index scores against ALL the keys, the
selection a plain ``top_k`` of them under the causal mask, then one
head's scores of the block at a time under a ``-inf`` mask of the whole
row, so that a sequence of 16384 fits; the six like layers walk ONE
compiled body (a scan whose trips take their own layer's parameters).  A selection
comes in and goes out as a mask of bits (``pack`` / ``unpack``: the
layout the program's ``ops/dsa.py`` documents, written here from that
description), for a caller that hands one implementation's selection
to the other; where one is handed in, ``attention`` says how it differs
from its own.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import common as ref

# Queries a block of index scores, and of one head's scores, holds
# against all the keys.
QUERY_BLOCK = 512
# Positions whose logits over the rows held the loss makes at a time.
LOSS_BLOCK = 2048
BITS = 32
# Gaps under a threshold at which ``attention`` counts the pairs a given
# selection holds and its own would not (``stray``).
STRAY_GAPS = (0.01, 0.03, 0.1, 0.3, 1.0)


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def matmul(x, kernel):
    return jnp.dot(x, kernel, precision=ref.HI)


def swiglu(x, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(x, w1)) * matmul(x, w3), w2)


def key_tile(seq: int) -> int:
    """Keys a tile of the mask of bits holds: 512, or the most up to
    the sequence that divide it in whole words."""
    return max(t for t in range(BITS, min(512, seq) + 1, BITS)
               if seq % t == 0)


def pack(keep):
    """``keep`` ``[queries, S]`` bool as ``[S / 32, queries]`` int32:
    word row ``c * (tile / 32) + r``, bit ``b``, is key ``c * tile + b *
    (tile / 32) + r``."""
    queries, seq = keep.shape
    tile = key_tile(seq)
    bits = keep.reshape(queries, seq // tile, BITS, tile // BITS)
    words = (bits.astype(jnp.uint32) << jnp.arange(
        BITS, dtype=jnp.uint32)[None, None, :, None]).sum(
            2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        queries, seq // BITS).T


def unpack(words):
    """``pack``'s inverse."""
    seq, queries = words.shape[0] * BITS, words.shape[1]
    tile = key_tile(seq)
    tiles = words.T.reshape(queries, seq // tile, 1, tile // BITS)
    bits = (tiles >> jnp.arange(BITS, dtype=jnp.int32)[None, None, :, None])
    return (bits & 1).reshape(queries, seq) != 0


def positions_of(batch, seq: int):
    """The three position streams ``[3, S]``: a batch's own
    (``positions``), or text's, the token's place three times."""
    given = batch.get("positions")
    if given is not None:
        return jnp.asarray(given, jnp.float32)
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.float32), (3, seq))


def rotate_halves(x, theta, positions, sections):
    """``x``: [b, s, heads, d].  Frequency pair ``i`` of a head (channel
    ``i`` with channel ``i + d / 2``) turns by ``positions[stream of i,
    t] * theta^(-2i / d)``; ``sections`` says how many consecutive
    pairs read each stream."""
    d = x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    stream = np.repeat(np.arange(len(sections)), sections)
    angle = (positions[stream].T * inverse)[:, None, :]    # [s, 1, d / 2]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def kept_by(masked, best, taken):
    """``[rows, S]`` bool: the keys whose indices ``taken`` a ``top_k``
    of ``masked`` returned beside their values ``best``, without a
    scatter of a million indices a block: every key over a row's least
    value taken, and of those AT it (ties: ``top_k`` takes the lower
    position first) the ones up to the last position it took."""
    least = best[:, -1:]
    last = jnp.where(best == least, taken, -1).max(-1, keepdims=True)
    at = jnp.arange(masked.shape[1])[None, :]
    return (masked > least) | ((masked == least) & (at <= last))


def indexer(u, p, config, positions):
    """``(q_I [b, s, J, d], k_I [b, s, d], w [b, s, J])`` from the
    layer's normed input, detached."""
    sa, theta = config["sa_config"], config["rope_theta"]
    u = jax.lax.stop_gradient(u)
    q_i = jnp.einsum("bsh,hjd->bsjd", u, p["indexer_query"]["kernel"],
                     precision=ref.HI)
    k_i = ref.layer_norm(matmul(u, p["indexer_key"]["kernel"]),
                         p["indexer_key_norm"], config["rms_norm_eps"])
    whole = [sa["indexer_head_dim"] // 2]
    q_i = rotate_halves(q_i, theta, positions[:1], whole)
    k_i = rotate_halves(k_i[:, :, None], theta, positions[:1], whole)[:, :, 0]
    w = matmul(u, p["indexer_weights"]["kernel"]) / jnp.sqrt(
        float(sa["indexer_num_heads"] * sa["indexer_head_dim"]))
    return q_i, k_i, w


def attention(x, p, config, positions, given=None, near_tie: float = 0.0):
    """``x`` [b, s, H], the layer's normed input.  ``p`` holds query
    ([H, heads, d]), key and value ([H, kv_heads, d]), the two norms'
    scales ([d]), out ([heads, d, H]) and the indexer's three matrices
    and its norm.  Returns ``(the attention's output, L_I, saw)``;
    ``given`` ([b, S / 32, S] bits) replaces the indexer's own
    selection in both, and ``saw`` says of every sequence: ``selected``,
    the indexer's OWN selection as bits; ``own_pairs``, how many pairs
    it holds; ``agree``, how many of those ``given`` holds too; and
    ``widest_gap``, how far under a query's threshold (its ``topk``-th
    largest score) the lowest-scored pair lies that ``given`` holds and
    the indexer would not, as a share of the distance from the
    threshold to the query's largest score (0 with no ``given``);
    ``stray``, how many such pairs lie further under than each of
    ``STRAY_GAPS``; and
    ``near``, as bits, the indexer's own selection and beside it every
    causal pair that lies no further under its query's threshold than
    ``near_tie`` of that distance: what another implementation may hold
    and be within a near-tie of this one."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    sections = config["rope_scaling"]["mrope_section"]
    topk = config["sa_config"]["topk"]
    heads = lambda name: jnp.einsum("bsh,hnd->bsnd", x, p[name]["kernel"],
                                    precision=ref.HI)
    q, k, v = heads("query"), heads("key"), heads("value")
    d, seq = q.shape[-1], x.shape[1]
    q = rotate_halves(rms_norm(q, p["query_norm"], eps), theta, positions,
                      sections)
    k = rotate_halves(rms_norm(k, p["key_norm"], eps), theta, positions,
                      sections)
    q_i, k_i, w = indexer(x, p, config, positions)
    group = q.shape[2] // k.shape[2]
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError("the reference walks whole blocks of %d queries"
                         % block)
    key_at = jnp.arange(seq)

    def one_sequence(of_sequence):
        q_s, k_s, v_s, qi_s, ki_s, w_s, given_s = of_sequence
        k_heads = jnp.repeat(jnp.moveaxis(k_s, 1, 0), group, 0)  # [n, s, d]
        v_heads = jnp.repeat(jnp.moveaxis(v_s, 1, 0), group, 0)

        @jax.checkpoint
        def one_block(start):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 0)
            dots = jnp.einsum("tjd,sd->tjs", cut(qi_s), ki_s,
                              precision=ref.HI)
            scores = (jax.nn.relu(dots) * cut(w_s)[:, :, None]).sum(1)
            causal = key_at[None, :] <= (start + jnp.arange(block))[:, None]
            masked = jnp.where(causal, scores, -jnp.inf)
            best, taken = jax.lax.top_k(masked, min(topk, seq))
            own = kept_by(masked, best, taken) & causal
            if given_s is None:
                keep = own
            else:
                keep = unpack(jax.lax.dynamic_slice_in_dim(
                    given_s, start, block, 1))
            # A query with fewer causal keys than ``topk`` has no
            # threshold (its last "best" is the mask's -inf): whatever
            # it keeps is its own.
            least, most = best[:, -1:], best[:, :1]
            under = jnp.where(
                jnp.isfinite(least),
                (jnp.where(jnp.isfinite(least), least, 0.0) - scores)
                / jnp.maximum(most - least, 1e-30), -jnp.inf)
            gap = jnp.where(keep & ~own, under, 0.0).max()
            stray = jnp.stack([(keep & ~own & (under > at)).sum()
                               for at in STRAY_GAPS])
            near = own | (causal & (under <= near_tie))

            @jax.checkpoint
            def one_head(total, of_head):
                q_h, k_h, v_h = of_head              # [block, d], [s, d] x 2
                s = jnp.einsum("qd,kd->qk", q_h, k_h,
                               precision=ref.HI) / jnp.sqrt(float(d))
                probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
                return total + probs, jnp.einsum("qk,kd->qd", probs, v_h,
                                                 precision=ref.HI)
            total, ctx = jax.lax.scan(
                one_head, jnp.zeros((block, seq), jnp.float32),
                (jnp.moveaxis(cut(q_s), 1, 0), k_heads, v_heads))
            pbar = jax.lax.stop_gradient(total / k_heads.shape[0])
            pbar = pbar / pbar.sum(-1, keepdims=True)
            log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
            some = keep & (pbar > 0.0)
            kl = jnp.where(some, pbar * (
                jnp.log(jnp.where(some, pbar, 1.0))
                - jnp.where(some, log_q, 0.0)), 0.0).sum(-1)
            return ctx, kl, {"selected": pack(own), "near": pack(near),
                             "own_pairs": own.sum(), "stray": stray,
                             "agree": (own & keep).sum(), "widest_gap": gap}
        ctx, kl, saw = jax.lax.map(one_block, jnp.arange(0, seq, block))
        # ctx: [blocks, n, block, d] -> [s, n, d]
        ctx = jnp.moveaxis(ctx, 1, 2).reshape(seq, -1, d)
        whole = lambda bits: jnp.moveaxis(bits, 0, 1).reshape(seq // BITS,
                                                               seq)
        saw = {"selected": whole(saw["selected"]), "near": whole(saw["near"]),
            "own_pairs": saw["own_pairs"].sum(), "agree": saw["agree"].sum(),
            "stray": saw["stray"].sum(0),
            "widest_gap": saw["widest_gap"].max()}
        return ctx, kl, saw
    ctx, kl, saw = jax.lax.map(
        one_sequence, (q, k, v, q_i, k_i, w, given))
    saw = jax.lax.stop_gradient(saw)
    return (jnp.einsum("bqnd,ndh->bqh", ctx, p["out"]["kernel"],
                       precision=ref.HI), kl.mean(), saw)


def gate_matrix(x, p, config, chosen=None):
    """``[T, E]`` gates, zero off the chosen, for tokens ``x`` [T, H],
    and the router's own choice ``[T, top_k]``.  ``chosen`` takes the
    choice from another implementation; scores and gates are still this
    one's."""
    probs = jax.nn.softmax(matmul(x, p["router"]), axis=-1)
    _, own = jax.lax.top_k(probs, config["num_experts_per_tok"])
    taken = own if chosen is None else chosen
    g = jnp.take_along_axis(probs, taken, axis=-1)
    if config["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, taken].set(g), own


def sparse_ffn(x, p, config, chosen=None):
    """Every routed expert held over every token, times its column of
    the gate matrix.  ``x``: [b, s, H]."""
    flat = x.reshape(-1, x.shape[-1])
    gates, own = gate_matrix(flat, p, config, chosen)
    held = p["gate"].shape[0]
    first = config.get("first_expert", 0)
    columns = gates[:, first:first + held].T[..., None]     # [held, T, 1]

    @jax.checkpoint
    def add_expert(total, of_expert):
        w1, w3, w2, column = of_expert
        return total + column * swiglu(flat, w1, w3, w2), None
    total, _ = jax.lax.scan(add_expert, jnp.zeros_like(flat),
                            (p["gate"], p["up"], p["down"], columns))
    return total.reshape(x.shape), own


def hidden_and_parts(params, batch, config, chosen=None, selected=None,
                     near_tie: float = 0.0):
    """The final hidden states, normed, the head's matrix [V, H], the
    sum over the layers of ``L_I``, and by layer what it saw: its
    router's own choice (``chosen``) and ``attention``'s report.
    ``chosen`` (``{layer: [T, top_k]}``) fixes the experts taken and
    ``selected`` (``{layer: [b, S / 32, S] bits}``) the keys kept, layer
    by layer."""
    p = ref.f32(params)
    eps, depth = config["rms_norm_eps"], config["num_hidden_layers"]
    ids = batch["input_ids"]
    positions = positions_of(batch, ids.shape[1])
    h = p["word_embeddings"]["embedding"][ids]

    @jax.checkpoint
    def layer(h, n, given_choice, given_selection):
        # A trip takes its own layer's parameters: stacked, the check's
        # program holds every layer's a second time.
        lp = jax.lax.switch(n, [lambda i=i: p["layer_%d" % i]
                                for i in range(depth)])
        attended, aligned, saw = attention(
            rms_norm(h, lp["input_norm"], eps), lp["attention"], config,
            positions, given_selection, near_tie)
        a = h + attended
        y, own = sparse_ffn(rms_norm(a, lp["post_attention_norm"], eps),
                            lp["moe"], config, given_choice)
        return a + y, (aligned, dict(saw, chosen=own))
    stack = lambda given: (None if not given else
                           jnp.stack([given[i] for i in range(depth)]))
    h, (aligned, saw) = jax.lax.scan(
        lambda h, of_layer: layer(h, *of_layer), h,
        (jnp.arange(depth), stack(chosen), stack(selected)))
    saw = {i: jax.tree.map(lambda a: a[i], saw) for i in range(depth)}
    return rms_norm(h, p["final_norm"], eps), p["lm_head"], aligned.sum(), saw


def loss_parts(params, batch, config: dict, chosen=None, selected=None):
    """``(L_lm, sum of L_I, saw)``.  ``params``: the tree of
    ``KeyeVLLMHeadModel``; ``batch``: ``input_ids`` of shape [B, S] and,
    for other positions than text's, ``positions`` [3, S].  Position t
    predicts token t + 1."""
    ids = batch["input_ids"]
    h, head, aligned, saw = hidden_and_parts(params, batch, config, chosen,
                                             selected)

    seq = ids.shape[1]
    block = min(LOSS_BLOCK, seq)
    if seq % block:
        raise ValueError("the reference's loss walks whole blocks of %d "
                         "positions" % block)

    def of_sequence(one):
        h_b, ids_b = one                 # [S, H], [S]

        @jax.checkpoint
        def of_block(of):
            h_c, targets = of            # a block of positions' logits
            return ref.cross_entropy(
                jnp.einsum("sh,vh->sv", h_c, head, precision=ref.HI), targets)
        nll = jax.lax.map(of_block, (
            h_b.reshape(seq // block, block, -1),
            jnp.roll(ids_b, -1).reshape(seq // block, block)))
        return nll.reshape(seq)[:-1].mean()   # the last position has no target
    return jax.lax.map(of_sequence, (h, ids)).mean(), aligned, saw


def loss(params, batch, config: dict, chosen=None, selected=None):
    lm, aligned, _ = loss_parts(params, batch, config, chosen, selected)
    return lm + aligned
