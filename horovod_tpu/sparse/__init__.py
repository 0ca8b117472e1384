"""Model-parallel sparse embedding engine — the recsys/DLRM workload.

Dense data-parallel training allreduces every gradient; DLRM-style
recommenders instead keep their dominant state — embedding tables with
millions of rows — **model-parallel**: each rank owns a slice of every
table, a training step looks up only the rows its batch touches, and
the lookup/gradient exchange is an **alltoall**, not an allreduce
(Check-N-Run, NSDI '22; see PAPERS.md).  This package opens that
traffic pattern on the existing eager plane:

* :class:`~.embedding.ShardedEmbedding` splits tables row-wise across
  ranks (round-robin by row id, so hot rows spread evenly), exchanges
  per-rank index batches and gathered rows through the
  splits-piggybacking ``hvd.alltoall`` (the coordinator hands every
  rank its recv splits in the negotiation response — no data-plane
  split exchange), and applies sparse gradient updates locally.
* Every update records its rows in a **touched-row set** per table
  since the last committed checkpoint, which is exactly what the
  differential checkpoint layer persists
  (:class:`horovod_tpu.checkpoint.RowDelta`): a periodic full base
  plus touched-rows-only deltas, cutting checkpoint bytes to the
  touch rate.
* :class:`~.embedding.EmbeddingBag` pools looked-up rows per example
  (sum/mean), the DLRM interaction-input shape.

The per-step split vectors legally vary with the batch, so cycles
containing these alltoalls are exactly the traffic steady-state
replay must never freeze — ``hvd_steady_state_exits{reason=alltoall}``
labels both the submit-side and delivery-side exits.

See docs/sparse_embedding.md for the exchange protocol and
models/dlrm.py for the workload.
"""

from .embedding import (EmbeddingBag, ShardedEmbedding,
                        lookup_overlapped)

__all__ = ["ShardedEmbedding", "EmbeddingBag", "lookup_overlapped"]
