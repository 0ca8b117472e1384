"""The Keye-VL family (the language model of Keye-VL-2.0: grouped-query
attention over the keys a learned indexer keeps for each query, rotary
positions in three sections, top-k of softmax-routed experts in every
layer): how the benchmark builds its step from the program, makes a
batch from the seed, counts the required FLOPs, and the new kernels',
and calls the reference.  Sizes come from the configuration file, never
from here.

The reference check and near-ties.  Two discrete decisions a layer: the
experts a token takes and the keys a query keeps.  Two compiles of a
low-precision forward pass do not decide every near-tie alike, and the
leaves downstream of a flipped decision feel it (``benchmarks/models/
afmoe.py`` has the readings), so BOTH sides of the comparison compute
on ONE choice and ONE selection, the float32 reference's own
(``reference_saw``), and what is compared at the fixed tolerances is
the continuous mathematics.  The program's own selection as it runs on
that stream is held to the reference's in ``system_loss``, by the two
limits below, or the batch has no loss; ``init`` reports both numbers a
layer, measured, on the first batch of the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models import common
from benchmarks.models.lfm2 import host_batch, optimizer  # noqa: F401  the
#   same batches (tokens of the rows held) and the same AdamW
from benchmarks.reference import keye_vl as reference
from benchmarks.trainers.common import info
from horovod_tpu.models import keye_vl
from horovod_tpu.models.layers import choices_of
from horovod_tpu.ops import dsa
from horovod_tpu.parallel import moe
from horovod_tpu.training import (DSA_SELECTION_AGREEMENT, keye_vl_loss_parts,
                                  keye_vl_step_loss, make_keye_vl_train_step)

# What the reference check holds the program's SELECTION to, since both
# sides compute on the reference's.  Of the pairs of query and key the
# reference's indexer keeps in a layer, the program's own (bfloat16
# index products, on the stream the reference's decisions made) keeps at
# least MIN_AGREEMENT; and the pairs it keeps that the reference would
# not are near-ties: all but MAX_STRAY of a layer's pairs lie no further
# under their query's threshold (the topk-th largest score) than
# NEAR_TIE of the distance from the threshold to the query's largest
# score.  A share and not the widest gap of all: a layer holds 31
# million pairs a sequence, and the widest of so many is the tail's to
# say, a query whose scores nearly coincide among them.  Each limit
# lies between two readings on the chip at 1 x 16384 and the published
# widths (PERF.md, PR 49): the program in bfloat16, and a program whose
# weights keep three bits of mantissa.
NEAR_TIE = 0.03
MIN_AGREEMENT = 0.98
MAX_STRAY = 5e-4


def program_config(config: dict) -> keye_vl.KeyeVLConfig:
    """``num_experts`` in the file counts the experts held; the router
    keeps the published width."""
    if config["tie_word_embeddings"]:
        raise ValueError("the program's model has a head of its own")
    if config["sliding_window"] is not None or config["use_sliding_window"]:
        raise ValueError("the program's attention has a selection and no "
                         "window")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer of the program is sparse")
    if config["attention_bias"]:
        raise ValueError("the program's projections have no bias")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer has one key head")
    if sa["kv_chunk_size"] != dsa.KEY_TILE \
            or sa["q_chunk_size"] != dsa.QUERY_BLOCK:
        raise ValueError("the program evaluates the selection in tiles of "
                         "%d keys and blocks of %d queries"
                         % (dsa.KEY_TILE, dsa.QUERY_BLOCK))
    return keye_vl.KeyeVLConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        topk=sa["topk"],
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["num_experts"],
        first_expert=config.get("first_expert", 0),
        norm_topk_prob=config["norm_topk_prob"],
        init_depth=config["published"]["num_hidden_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(config.get("remat", False)))


def pair_flops(config: dict) -> dict:
    """Forward FLOPs of ONE pair of query and key in one layer:
    ``attention``, every query head's score and its weighted value (2 x
    2 x head_dim a head: 16384 at 32 heads of 128); ``indexer``, every
    index head's product and its share of the weighted sum of ``relu``s
    (2 x indexer_head_dim + 2 a head: 2080 at 16 heads of 64)."""
    sa = config["sa_config"]
    return {"attention": config["num_attention_heads"] * 2 * 2
            * config["head_dim"],
            "indexer": sa["indexer_num_heads"]
            * (2 * sa["indexer_head_dim"] + 2)}


def flops_per_token(config: dict, seq: int) -> float:
    """Required forward FLOPs a token at sequences of ``seq``: the head
    over the rows held; a layer's projections (query and output at the
    query heads' width, key and value at the key-value heads', the
    indexer's three), attention at the SELECTED pairs, the indexer at
    every CAUSAL pair (it has to score a key to leave it out), the
    router over all experts and the routed experts held at the
    expectation under uniform routing (one expert a token at 8 of 128
    with 16 held)."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    d, sa = config["head_dim"], config["sa_config"]
    total = config["published"]["num_experts"]
    projections = 2 * hidden * heads * d \
        + 2 * hidden * config["num_key_value_heads"] * d \
        + hidden * sa["indexer_head_dim"] * (sa["indexer_num_heads"] + 1) \
        + hidden * sa["indexer_num_heads"]
    expected = config["num_experts_per_tok"] * config["num_experts"] / total
    per_pair = pair_flops(config)
    layer = 2 * projections + 2 * hidden * total \
        + expected * 3 * 2 * hidden * config["moe_intermediate_size"] \
        + per_pair["attention"] * dsa.selected_pairs(seq, sa["topk"]) / seq \
        + per_pair["indexer"] * dsa.causal_pairs(seq) / seq
    return 2.0 * hidden * config["vocab_size"] \
        + config["num_hidden_layers"] * layer


def flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Required forward and backward FLOPs of one step (backward twice
    forward).  Recomputation, the selection itself (counts and
    compares), the alignment loss's second look at the main attention's
    scores, padded rows and masked scores count nothing: a kernel that
    walks the triangle to attend to a quarter of it does work that is
    not counted."""
    return common.train_flops(flops_per_token(config, seq) * batch * seq)


def flash_kernel_work(config: dict, batch: int, seq: int) -> dict:
    """``{kernel: (FLOPs, HBM bytes)}`` of one call of each selected
    flash kernel on ``[batch, seq, heads, head_dim]`` (one layer, one
    pass) at the SELECTED pairs: the forward makes scores and the
    weighted sum, the fused backward scores, ``dV``, ``dP``, ``dK`` and
    ``dQ``.  Bytes: every operand read once and every result written
    once (keys and values as the kernels take them, laid out a query
    head; the selection's bits once), the row statistics in float32."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    itemsize = np.dtype(config["compute_dtype"]).itemsize
    rows = batch * seq * heads
    wide, stat = rows * d * itemsize, rows * 4
    bits = batch * seq * seq // 8
    product = 2.0 * batch * heads * d * dsa.selected_pairs(
        seq, config["sa_config"]["topk"])
    return {"hvd_flash_fwd_selected": (2 * product, 4 * wide + stat + bits),
            "hvd_flash_bwd_selected": (5 * product,
                                       8 * wide + 2 * stat + bits)}


def indexer_kernel_work(config: dict, batch: int, seq: int) -> dict:
    """``{kernel: (FLOPs, HBM bytes)}`` of one call of each indexer
    kernel (one layer).  ``hvd_dsa_select``: the index scores of every
    causal pair; it reads the index queries, keys and weights and
    writes the bits and a statistic a query.  ``hvd_dsa_indexer_loss``:
    the scores' backward at every causal pair (twice the forward: the
    gradients to the index queries and keys) and every head's score of
    the main attention at the SELECTED pairs, whose mean is the target;
    it reads both attentions' queries and keys, the bits and the
    statistics and writes the indexer's three gradients, the keys' a
    block of queries at a time.  What either spends making the scores a
    second time is recomputation and not counted.  Both are products:
    the peak that binds them is the matrix unit's."""
    sa = config["sa_config"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    itemsize = np.dtype(config["compute_dtype"]).itemsize
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    causal = batch * dsa.causal_pairs(seq)
    selected = batch * dsa.selected_pairs(seq, sa["topk"])
    tokens = batch * seq
    of_index = tokens * ((index + sa["indexer_head_dim"]) * itemsize
                         + sa["indexer_num_heads"] * 4)
    bits = tokens * seq // 8
    blocks = -(-seq // dsa.LOSS_QUERIES)
    return {
        "hvd_dsa_select": (
            float(pair_flops(config)["indexer"] * causal),
            of_index + bits + tokens * 4),
        "hvd_dsa_indexer_loss": (
            2.0 * pair_flops(config)["indexer"] * causal
            + 2.0 * heads * d * selected,
            of_index + bits + tokens * (
                (heads + config["num_key_value_heads"]) * d * itemsize
                + heads * 4 + 8)
            + of_index + blocks * tokens * sa["indexer_head_dim"] * 4)}


def ingraph(config: dict, mesh, example_batch) -> common.InGraph:
    del example_batch  # the builder needs no shapes beforehand
    init_fn, step_fn, batch_sharding = make_keye_vl_train_step(
        program_config(config), mesh,
        learning_rate=config["optimizer"]["learning_rate"],
        weight_decay=config["optimizer"]["weight_decay"])
    report = jax.jit(lambda params, ids: selection_report(config, params,
                                                          ids))

    def init(key, batch):
        state = init_fn(key, batch["input_ids"])
        say(jax.device_get(report(state[0], batch["input_ids"][:1])))
        return state

    def step(state, batch):
        params, opt_state, loss = step_fn(*state, batch["input_ids"])
        return (params, opt_state), loss

    def hlo_text(state, batch):
        return step_fn.lower(*state, batch["input_ids"]).compile().as_text()

    return common.InGraph(init, step, lambda state: state[0], hlo_text,
                          batch_sharding)


def init_params(config: dict, key, batch):
    return keye_vl.KeyeVLLMHeadModel(program_config(config)).init(
        key, batch["input_ids"])["params"]


def train_loss(config: dict):
    """The loss of ``make_keye_vl_train_step``'s step itself."""
    model = keye_vl.KeyeVLLMHeadModel(program_config(config))

    def loss(params, batch, step):
        del step
        return keye_vl_step_loss(model, params, batch["input_ids"])
    return loss


def reference_saw(config: dict, params, ids):
    """What the float32 reference decides on its OWN stream for ``ids``
    ``[1, S]`` (``{layer: {"chosen": [T, top_k], "selected": bits,
    "near": bits, ...}}``, no gradient): BOTH sides of the comparison
    compute on that ``chosen`` and that ``selected``.  The two sides are
    two compiled programs; a product at full precision differs between
    two compiles by the order of a float32 sum, which moves no
    threshold that a bfloat16 rounding would not move a thousand times
    further."""
    params, ids = jax.lax.optimization_barrier(
        (jax.lax.stop_gradient(params), ids))
    with jax.default_matmul_precision("highest"):
        saw = reference.hidden_and_parts(
            params, {"input_ids": ids}, config, near_tie=NEAR_TIE)[3]
    return jax.lax.optimization_barrier(saw)


def _decisions(saw: dict):
    return ({layer: s["chosen"] for layer, s in saw.items()},
            {layer: s["selected"] for layer, s in saw.items()})


def _a_sequence_at_a_time(loss_of_one, recompute: bool):
    """``loss(params, batch)``, the mean over the batch's sequences of
    ``loss_of_one(params, ids [1, S])``, one sequence after another: a
    step of the timed shape (one sequence a chip) a trip, and one
    sequence's activations alive; with ``recompute`` a trip keeps
    nothing for the backward pass and is made again there.  The check's
    programs are loaded beside the whole training state."""
    def loss(params, batch):
        def trip(params, ids):
            return loss_of_one(params, ids[None])
        trip = jax.checkpoint(trip) if recompute else trip
        return jax.lax.map(lambda ids: trip(params, ids),
                           batch["input_ids"]).mean()
    return loss


def _pairs(bits):
    return jax.lax.population_count(bits).sum()


def selections_agree(own: dict, saw: dict):
    """Whether the program's own selection (``{layer: bits}``) lies
    within the limits of the reference's in every layer: it keeps
    ``MIN_AGREEMENT`` of the reference's pairs, and no more than
    ``MAX_STRAY`` of as many outside the reference's ``near`` (its own
    and the pairs within ``NEAR_TIE`` under a threshold)."""
    return jnp.all(jnp.stack([
        (_pairs(own[layer] & s["selected"])
         >= MIN_AGREEMENT * _pairs(s["selected"]))
        & (_pairs(own[layer] & ~s["near"])
           <= MAX_STRAY * _pairs(s["selected"]))
        for layer, s in saw.items()]))


def system_loss(config: dict, program_params=None):
    """The step's loss on the reference's own decisions, and no loss
    (nan) where the program's own selection on that stream is not the
    reference's but for near-ties (``selections_agree``); keeping across
    ``remat`` the decisions it is handed and what its indexer makes, and
    nothing else.  ``program_params`` (a function of the parameters)
    gives the indexers that select other weights than the rest (a test
    of the limits rounds them)."""
    model = keye_vl.KeyeVLLMHeadModel(
        program_config(config),
        remat_names=dsa.DSA_NAMES + (moe.CHOICE_NAME,))

    def of_one(params, ids):
        saw = reference_saw(config, params, ids)
        lm, aligned, sown = keye_vl_loss_parts(model, params, ids,
                                               *_decisions(saw))
        own = keye_vl.sown_of(sown, "selected")
        if program_params is not None:
            own = program_selection(config, program_params(params), ids,
                                    *_decisions(saw))
        return jnp.where(selections_agree(own, saw), lm + aligned, jnp.nan)
    return _a_sequence_at_a_time(of_one, recompute=False)


def reference_loss(config: dict):
    """The reference on its own decisions, which the system computes on
    too (``reference_saw``: the same forward pass, compiled beside the
    system's side; a product at full precision differs between two
    compiles, and between a pass and its recomputation, by the order of
    a float32 sum, so a pair in thirty million may fall the other side
    of a threshold, which moves no loss and no leaf that the tolerances
    could see)."""
    def of_one(params, ids):
        return reference.loss(params, {"input_ids": ids}, config)
    # Recomputed: what the reference's layers keep in float32 would
    # otherwise wait for the backward pass beside the training state.
    return _a_sequence_at_a_time(of_one, recompute=True)


def program_decisions(config: dict, params, ids, chosen=None, selected=None):
    """``(the experts every token takes, the keys every query's indexer
    keeps)`` in the program as it runs on ``ids``, in bfloat16
    (``{layer: [T, top_k]}`` and ``{layer: bits}``, no gradient), every
    layer reading the stream that ``chosen`` and ``selected`` (its own
    where None) made in the layers before it; behind a barrier, in the
    program's own precision."""
    params, ids = jax.lax.optimization_barrier(
        (jax.lax.stop_gradient(params), ids))
    with jax.default_matmul_precision("default"):
        model = keye_vl.KeyeVLLMHeadModel(program_config(config))
        sown = keye_vl_loss_parts(model, params, ids, chosen, selected)[2]
    return jax.lax.optimization_barrier(
        (choices_of(sown), keye_vl.sown_of(sown, "selected")))


def program_selection(config: dict, params, ids, chosen=None, selected=None):
    """``program_decisions``'s selection alone."""
    return program_decisions(config, params, ids, chosen, selected)[1]


def selection_report(config: dict, params, ids, program_params=None) -> dict:
    """By layer, MEASURED: the share of the reference's pairs that the
    program's own selection keeps, and the widest gap under a threshold
    of a pair it keeps and the reference would not, the reference's
    indexer reading the stream that the PROGRAM's decisions made in the
    layers before it, so that what it tells of a layer is that layer's
    own near-ties."""
    chosen, took = program_decisions(
        config, params if program_params is None else program_params, ids)
    with jax.default_matmul_precision("highest"):
        saw = reference.hidden_and_parts(
            params, {"input_ids": ids}, config, chosen, took)[3]
    first, held = config.get("first_expert", 0), config["num_experts"]
    on_held = lambda c: ((c >= first) & (c < first + held)).sum()
    return {layer: {"agreement": s["agree"].sum() / s["own_pairs"].sum(),
                    "pairs_held": on_held(chosen[layer]),
                    "widest_gap": s["widest_gap"].max(),
                    "stray": s["stray"].sum(0),
                    "pairs": s["own_pairs"].sum()}
            for layer, s in saw.items()}


def say(report: dict):
    """The report's lines, and the gauge."""
    for layer, r in sorted(report.items()):
        stray = [int(n) for n in np.asarray(r["stray"]).reshape(-1)]
        r = {k: float(v) for k, v in r.items() if k != "stray"}
        DSA_SELECTION_AGREEMENT.set(r["agreement"], layer=str(layer))
        info("attention layer %d: the program's own selection keeps %.4f of "
             "the float32 reference's %d pairs (at least %.3f), the widest "
             "gap under a threshold %.2e of the way to the largest score; "
             "pairs further under than %s: %s (at most %d further than "
             "%.2f); %d pairs of token and expert on the experts held"
             % (layer, r["agreement"], r["pairs"], MIN_AGREEMENT,
                r["widest_gap"], list(reference.STRAY_GAPS), stray,
                MAX_STRAY * r["pairs"], NEAR_TIE, r["pairs_held"]))
