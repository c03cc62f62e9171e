"""Model FLOPs of a training round, from a configuration's shapes.

Counts the matrix products the forward and backward passes require (the
backward twice the forward), the tied output head among them, and of
causal attention only the unmasked query-key pairs.  Recomputation,
masked attention blocks, norms, activations, the loss, the optimizer and
the gossip mix are not model FLOPs and are not counted.
"""

from __future__ import annotations

from weights import Dims


def forward_flops_per_sequence(d: Dims, seq: int) -> int:
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    layer_weights = d.d_model * (2 * q + 2 * kv) + 3 * d.d_model * d.d_ff
    matmuls = 2 * seq * (d.layers * layer_weights + d.d_model * d.vocab)
    pairs = seq * (seq + 1) // 2  # causal query-key pairs
    attention = d.layers * 2 * 2 * pairs * q  # scores and values
    return matmuls + attention


def round_flops(d: Dims, workers: int, batch_per_worker: int, seq: int) -> int:
    """Forward and backward of every worker's batch in one round."""
    return 3 * workers * batch_per_worker * forward_flops_per_sequence(d, seq)
