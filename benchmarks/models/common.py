"""What every family module counts the same way."""

from typing import Any, Callable, NamedTuple


def encoder_flops_per_token(hidden: int, ffn: int, layers: int,
                            attended: float) -> float:
    """Forward multiply-adds times two, per token, of ``layers``
    transformer blocks: four H x H projections (query, key, value, out),
    the two H x FFN products, and attention's scores and weighted sum
    over ``attended`` keys per query (S for a full square, S / 2 for a
    causal one)."""
    projections = 4 * 2 * hidden * hidden
    mlp = 2 * 2 * hidden * ffn
    attention = 2 * 2 * attended * hidden
    return layers * (projections + mlp + attention)


def train_flops(forward_flops: float) -> float:
    """Forward and backward: the backward pass computes two products
    for each of the forward's (one for the input, one for the weight).
    A recomputed forward is not required work and is not counted."""
    return 3.0 * forward_flops


class InGraph(NamedTuple):
    """A sharded step builder of ``training.py`` behind one face."""
    init: Callable        # (key, batch) -> state
    step: Callable        # (state, batch) -> (state, loss)
    params: Callable      # state -> parameter tree
    hlo_text: Callable    # (state, batch) -> text of the compiled step,
    #                       whose instructions carry the module paths
    #                       the trace lacks
    batch_sharding: Any   # what a batch is placed with
