"""Model FLOPs of a training round.

A model's module (``models/``) counts the matrix products of one sequence's
forward pass (``forward_flops_per_sequence``); the backward pass is twice
the forward.  Recomputation, masked attention blocks, norms, activations,
the loss, the optimizer and the gossip mix are not model FLOPs and are not
counted.
"""

from __future__ import annotations


def round_flops(model, d, workers: int, batch_per_worker: int, seq: int) -> int:
    """Forward and backward of every worker's batch in one round."""
    return 3 * workers * batch_per_worker * model.forward_flops_per_sequence(d, seq)
