"""Sparse-attention indexer (``models/transformer_lm.py``
``SparseIndexer``): device milliseconds a step in the operations traced
under the flax module ``indexer`` (scopes ``indexer/project``,
``indexer/scores``, ``indexer/select``, ``indexer/loss``): its three
projections, the index scores in query chunks, the bisection for each
row's threshold and the selection it gives, and the KL loss towards the
attention's head-summed probabilities, forward, recomputed and backward.
The kernel that sums those probabilities is attention's
(``sparse_attention_roofline``). The union of their intervals
(``benchmark/block_time.py``: the block's loops show as ``while``
operations that span their bodies), from the device trace joined to the
compiled step's scopes (``benchmark/scopes.py``); reads nothing on a
program whose scope table has no such block."""

from benchmark import block_time


def read(ctx):
    return block_time.union_ms_per_step(ctx, "indexer")
