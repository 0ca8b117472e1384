"""Path-based partition rules: map parameter/optimizer pytrees onto the
device mesh.

This is the GSPMD analog of the reference's per-tensor dispatch: instead
of shipping each tensor to a collective backend at runtime, tensors are
*annotated* with mesh placements and XLA inserts the collectives
(psum/all-gather/reduce-scatter) during compilation — the scaling-book
recipe.  Rules are (regex, PartitionSpec) pairs matched against
"/"-joined pytree paths, so the same rules shard params AND their
mirrored optimizer moments (mu/nu subtrees repeat the param paths).
"""

import re
from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[Tuple[str, P]]


def _transformer_partition_rules(tp: str, fsdp: Optional[str],
                                 extra: Rules = ()) -> Rules:
    """Megatron-style tensor parallelism shared by both transformer
    families (models/bert.py naming == models/gpt.py naming): QKV
    projections column-parallel over heads, attention output
    row-parallel, MLP in column- / out row-parallel, embeddings
    vocab-sharded.  ``extra`` prepends family-specific rules."""
    f = fsdp  # optional second sharding axis (ZeRO-3 style)
    return [
        *extra,
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"position_embeddings/embedding$", P(None, f)),
        (r"attention/(query|key|value)/kernel$", P(f, tp, None)),
        (r"attention/(query|key|value)/bias$", P(tp, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"attention/out/bias$", P(None)),
        (r"intermediate/kernel$", P(f, tp)),
        (r"intermediate/bias$", P(tp)),
        (r"(layer_\d+/)output/kernel$", P(tp, f)),
        (r".*", P()),  # everything else (norms, small biases) replicated
    ]


def bert_partition_rules(tp: str = "tp",
                         fsdp: Optional[str] = None) -> Rules:
    """Tensor-parallel sharding for the flax BERT encoder family."""
    return _transformer_partition_rules(tp, fsdp, extra=[
        (r"token_type_embeddings/embedding$", P(None, fsdp)),
        (r"mlm_transform/kernel$", P(None, fsdp)),
        (r"mlm_bias$", P(tp)),
    ])


def gpt_partition_rules(tp: str = "tp",
                        fsdp: Optional[str] = None) -> Rules:
    """Tensor-parallel sharding for the GPT decoder family (the tied
    LM head inherits the embedding's vocab sharding)."""
    return _transformer_partition_rules(tp, fsdp)


def granite_partition_rules(tp: str = "tp",
                            fsdp: Optional[str] = None) -> Rules:
    """Tensor-parallel sharding for the Granite hybrid family
    (models/granite.py): attention over its heads (query heads and, in
    step with them, key-value heads: ``tp`` must divide both), the MLP
    over its columns, the Mamba-2 mixer over its heads from the
    recurrence on (per-head vectors, the output projection's rows).
    ``in_proj`` stays whole across ``tp``: its columns are five unequal
    runs (z, x, B, C, dt) of which B and C serve every head, so no even
    split of them follows the heads; the step constrains ``x`` to the
    heads' sharding after the split.  The tied head inherits the
    embedding's vocabulary sharding."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"attention/(query|key|value)/kernel$", P(f, tp, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"mlp/(gate|up)/kernel$", P(f, tp)),
        (r"mlp/out/kernel$", P(tp, f)),
        (r"mamba/in_proj/kernel$", P(f, None)),
        (r"mamba/out_proj/kernel$", P(tp, f)),
        (r"mamba/(A_log|dt_bias|D)$", P(tp)),
        (r"mamba/norm/scale$", P(tp)),
        (r".*", P()),  # norms and the convolution replicated
    ]


def lfm2_partition_rules(tp: str = "tp", fsdp: Optional[str] = None,
                         ep: str = "ep") -> Rules:
    """Sharding for the LFM2-MoE family (models/lfm2.py).  Over ``tp``:
    attention over its heads (``tp`` must divide the key-value heads),
    the dense SwiGLU and every expert over their columns, the
    convolution operator's output projection over its rows; its input
    projection stays whole (its columns are the three runs B, C, x,
    which an even split does not follow).  The stacked experts' leading
    axis lies on ``ep`` where the mesh has one; the router, over all
    experts, is whole everywhere.  No exchange is written for ``ep``
    yet: GSPMD gathers what ``routed_experts`` reads (ROADMAP.md,
    Queue 2).  The tied head inherits the embedding's vocabulary
    sharding."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"attention/(query|key|value)/kernel$", P(f, tp, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"mlp/(gate|up)/kernel$", P(f, tp)),
        (r"mlp/out/kernel$", P(tp, f)),
        (r"conv/in_proj/kernel$", P(f, None)),
        (r"conv/out_proj/kernel$", P(tp, f)),
        (r"moe/(gate|up)$", P(ep, f, tp)),
        (r"moe/down$", P(ep, tp, f)),
        (r".*", P()),  # norms, taps, the router and its bias replicated
    ]


def deepseek_v3_partition_rules(tp: str = "tp", fsdp: Optional[str] = None,
                                ep: str = "ep") -> Rules:
    """Sharding for the DeepSeek-V3 family (models/deepseek_v3.py).
    Over ``tp``: latent attention over its heads (the query, the
    key-value up-projection and the output projection; the narrow
    down-projection and the latent's norm, which every head reads, stay
    whole), the dense SwiGLU, the shared expert and every routed expert
    over their columns.  The stacked experts' leading axis lies on
    ``ep`` where the mesh has one, the router whole everywhere, and no
    exchange is written for ``ep`` yet, as for
    :func:`lfm2_partition_rules`.  The embedding and the head, two
    matrices, both by rows of the vocabulary."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"lm_head$", P(tp, f)),
        (r"attention/(query|kv_up)/kernel$", P(f, tp, None)),
        (r"attention/kv_down/kernel$", P(f, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"(mlp|moe/shared)/(gate|up)/kernel$", P(f, tp)),
        (r"(mlp|moe/shared)/out/kernel$", P(tp, f)),
        (r"moe/(gate|up)$", P(ep, f, tp)),
        (r"moe/down$", P(ep, tp, f)),
        (r".*", P()),  # norms, the router and its bias replicated
    ]


def afmoe_partition_rules(tp: str = "tp", fsdp: Optional[str] = None,
                          ep: str = "ep") -> Rules:
    """Sharding for the AFMoE family (models/afmoe.py).  Over ``tp``:
    attention over its heads (the query, the gate, whose columns are
    the query heads' channels, the output projection, and the fused key
    and value where ``tp`` divides the key-value heads), the dense
    SwiGLU, the shared expert and every routed expert over their
    columns.  The stacked experts' leading axis lies on ``ep`` where
    the mesh has one, the router and its selection bias whole
    everywhere (the rule that moves the bias counts ALL experts'
    loads), and no exchange is written for ``ep`` yet, as for
    :func:`lfm2_partition_rules`.  The embedding and the head, two
    matrices, both by rows of the vocabulary."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"lm_head$", P(tp, f)),
        (r"attention/(query|key_value|gate)/kernel$", P(f, tp, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"(mlp|moe/shared)/(gate|up)/kernel$", P(f, tp)),
        (r"(mlp|moe/shared)/out/kernel$", P(tp, f)),
        (r"moe/(gate|up)$", P(ep, f, tp)),
        (r"moe/down$", P(ep, tp, f)),
        (r".*", P()),  # norms, the router and its bias replicated
    ]


def keye_vl_partition_rules(tp: str = "tp", fsdp: Optional[str] = None,
                            ep: str = "ep") -> Rules:
    """Sharding for the Keye-VL family (models/keye_vl.py).  Attention
    and its indexer are whole on every device of ``tp`` (the alignment
    loss reads ALL heads' probabilities of a query, and one selection
    serves them all): data-parallel, as the published deployment has
    them.  Over ``tp``: every routed expert over its columns, the
    embedding and the head, two matrices, by rows of the vocabulary.
    The stacked experts' leading axis lies on ``ep`` where the mesh has
    one, the router whole everywhere, and no exchange is written for
    ``ep`` yet, as for :func:`lfm2_partition_rules`."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"lm_head$", P(tp, f)),
        (r"attention/(query|key|value|indexer_query)/kernel$",
         P(f, None, None)),
        (r"attention/out/kernel$", P(None, None, f)),
        (r"moe/(gate|up)$", P(ep, f, tp)),
        (r"moe/down$", P(ep, tp, f)),
        (r".*", P()),  # norms, the router, the indexer's key and weights
    ]


def qwen3_next_partition_rules(tp: str = "tp", fsdp: Optional[str] = None,
                               ep: str = "ep") -> Rules:
    """Sharding for the Qwen3-Next family (models/qwen3_next.py).  Over
    ``tp``: full attention over its heads (the query with its gate, the
    output projection, and key and value where ``tp`` divides the
    key-value heads), the delta-rule mixer over its value heads from
    the recurrence on (the per-head vectors, the output projection's
    rows), the shared expert and every routed expert over their
    columns.  The mixer's ``in_proj`` stays whole across ``tp``, as
    :func:`granite_partition_rules` keeps its own and for its reason:
    its columns are six unequal runs (q, k, v, z, b, a) of which q and
    k serve two value heads each, so no even split of them follows the
    heads; the step constrains q, k and v to the heads' sharding after
    the split.  The stacked experts' leading axis lies on ``ep`` where
    the mesh has one, the router whole everywhere, and no exchange is
    written for ``ep`` yet, as for :func:`lfm2_partition_rules`.  The
    embedding and the head, two matrices, both by rows of the
    vocabulary."""
    f = fsdp
    return [
        (r"word_embeddings/embedding$", P(tp, f)),
        (r"lm_head$", P(tp, f)),
        (r"attention/(query|key|value)/kernel$", P(f, tp, None)),
        (r"attention/out/kernel$", P(tp, None, f)),
        (r"linear_attention/in_proj/kernel$", P(f, None)),
        (r"linear_attention/out_proj/kernel$", P(tp, f)),
        (r"linear_attention/(A_log|dt_bias)$", P(tp)),
        (r"moe/shared/(gate|up)/kernel$", P(f, tp)),
        (r"moe/shared/out/kernel$", P(tp, f)),
        (r"moe/(gate|up)$", P(ep, f, tp)),
        (r"moe/down$", P(ep, tp, f)),
        (r".*", P()),  # norms, taps, the router, the shared gate replicated
    ]


def resnet_partition_rules(fsdp: Optional[str] = None) -> Rules:
    """ResNet is pure data parallel (conv kernels are small); optionally
    ZeRO-shard the dense head."""
    return [
        (r"Dense_0/kernel$", P(fsdp, None) if fsdp else P()),
        (r".*", P()),
    ]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Adapt a rule's spec to a concrete leaf: drop axes the shape can't
    host (rank mismatch or non-divisible dims) so tiny dry-run shapes
    still compile."""
    ndim = len(shape)
    parts = list(spec)
    if len(parts) > ndim:
        parts = parts[:ndim]
    while len(parts) < ndim:
        parts.append(None)
    fitted = []
    for dim, ax in zip(shape, parts):
        if ax is None:
            fitted.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a not in mesh.shape for a in axes):
            # Rule names an axis this mesh doesn't have (e.g. tp rules on
            # a dp-only mesh): replicate that dimension.
            fitted.append(None)
            continue
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        fitted.append(ax if dim % total == 0 and dim > 0 else None)
    return P(*fitted)


def infer_shardings(tree, mesh: Mesh, rules: Rules):
    """Produce a pytree of NamedShardings matching ``tree``'s structure.

    Scalars/0-d leaves are replicated.  Works on params and on optimizer
    states (whose subtrees repeat parameter paths).
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def leaf_sharding(path, leaf):
        shape = getattr(leaf, "shape", ())
        if not shape:
            return NamedSharding(mesh, P())
        s = _path_str(path)
        for pat, spec in compiled:
            if pat.search(s):
                return NamedSharding(mesh, _fit_spec(spec, shape, mesh))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf_sharding, tree)


# A leaf under this many elements keeps its gradient on XLA's combined
# all-reduce and its optimizer state whole on every chip: a collective
# costs its latency whatever it carries, and XLA sums a model's vectors
# (biases, norms: BERT-large has 247 leaves of at most 30522 floats,
# a thousandth of its parameters) in one operation, where an exchange a
# leaf would be hundreds.  The smallest matrix there has 524288.
DATA_AXIS_MIN_ELEMENTS = 1 << 16


def shard_over_data_axis(tree, shardings, mesh: Mesh, axis: str = "dp"):
    """From the parameters' shardings to their gradients' and their
    optimizer moments': ``shardings`` (``infer_shardings`` of ``tree``,
    which is the parameters or an optimizer state that mirrors them)
    with ``axis`` added to each leaf of ``DATA_AXIS_MIN_ELEMENTS`` or
    more, on its first dimension that the rules left free and the axis'
    size divides (``_fit_spec``'s test).  A gradient constrained to that
    sharding is reduce-scattered over ``axis`` where it would have been
    all-reduced, and the optimizer's update runs on a chip's part of
    the leaf against its part of the moments (weight-update sharding,
    Xu et al. 2020, arXiv:2004.13336).  Every other leaf keeps its
    sharding; on an ``axis`` of one, or a mesh without it,
    ``shardings`` itself comes back."""
    shards = mesh.shape.get(axis, 1)
    if shards == 1:
        return shardings

    def with_axis(leaf, sharding):
        if leaf.size < DATA_AXIS_MIN_ELEMENTS:
            return sharding
        spec = list(sharding.spec) + [None] * (leaf.ndim - len(sharding.spec))
        for dim, (n, taken) in enumerate(zip(leaf.shape, spec)):
            if taken is None and n % shards == 0:
                spec[dim] = axis
                return NamedSharding(mesh, P(*spec))
        return sharding

    return jax.tree.map(with_axis, tree, shardings)


def gather_over_data_axis(tree, shardings, mesh: Mesh, axis: str = "dp"):
    """``tree``, laid out by ``shardings`` (``shard_over_data_axis``'s),
    whole again over ``axis``: an ``all_gather`` a leaf along the
    dimension that holds ``axis``, the other leaves as they are.

    Written out under ``shard_map`` (over ``axis`` alone: the mesh's
    other axes stay GSPMD's) and not left to a sharding constraint: the
    TPU compiler runs these gathers beside the backward pass's matmuls
    (141 of BERT-large's 148 as ``async-collective-start`` / ``-done``
    pairs), where it runs 109 of the gathers the partitioner writes for
    a constraint one after another: 10.2 ms of a dp4 step for 1.5, the
    step 148.60 ms for 143.20 (PERF.md, PR 31)."""
    def only_axis(sharding):
        return P(*(axis if ax == axis else None for ax in sharding.spec))

    def gather(tree):
        return jax.tree.map(
            lambda leaf, sharding: jax.lax.all_gather(
                leaf, axis, axis=sharding.spec.index(axis), tiled=True)
            if axis in sharding.spec else leaf, tree, shardings)

    specs = jax.tree.map(only_axis, shardings)
    # The gathered leaves are the same on every chip by construction,
    # which check_vma cannot see.
    return jax.shard_map(
        gather, mesh=mesh, in_specs=(specs,),
        out_specs=jax.tree.map(lambda _: P(), specs), axis_names={axis},
        check_vma=False)(tree)


def shard_tree(tree, mesh: Mesh, rules: Rules):
    """Device-put a pytree according to the rules (for seeding initial
    state onto the mesh)."""
    shardings = infer_shardings(tree, mesh, rules)
    return jax.tree.map(jax.device_put, tree, shardings)
