"""Jit'd dispatchers for the Pallas kernels.

``use_pallas`` picks the execution path:
  * True  -> compiled Pallas (TPU)
  * False -> pure-jnp reference (XLA; used for dry-run lowering on CPU)
  * "interpret" -> Pallas interpret mode (CPU correctness testing)

Default: Pallas on TPU backends, reference elsewhere.  ``rwkv_scan`` does
not compile for a TPU (its docstring says why), so ``rwkv`` needs
``use_pallas=False`` there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_mix import gossip_mix, gossip_mix_rows
from repro.kernels.rwkv_scan import rwkv_scan


def _default_mode():
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, use_pallas=None, block_q=128, block_k=128):
    mode = _default_mode() if use_pallas is None else use_pallas
    if mode == "interpret":
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=True)
    if mode:
        return flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    return ref.reference_attention(q, k, v, causal=causal)


def rwkv(r, k, v, w, u, *, use_pallas=None, chunk=64):
    mode = _default_mode() if use_pallas is None else use_pallas
    if mode == "interpret":
        return rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    if mode:
        return rwkv_scan(r, k, v, w, u, chunk=chunk)
    return ref.reference_rwkv(r, k, v, w, u)


def mix(x, u, pulled, w, *, use_pallas=None):
    mode = _default_mode() if use_pallas is None else use_pallas
    if mode == "interpret":
        return gossip_mix(x, u, pulled, w, interpret=True)
    if mode:
        return gossip_mix(x, u, pulled, w)
    return ref.reference_gossip_mix(x, u, pulled, w)


def mix_rows(x, u, pulled, w, *, use_pallas=None):
    """Stacked mix with per-row weights (leading worker/cohort axis)."""
    mode = _default_mode() if use_pallas is None else use_pallas
    if mode == "interpret":
        return gossip_mix_rows(x, u, pulled, w, interpret=True)
    if mode:
        return gossip_mix_rows(x, u, pulled, w)
    return ref.reference_gossip_mix_rows(x, u, pulled, w)


def segment_mean_rows(x, seg, num_segments):
    """Replace each row of ``x`` by the mean of the rows sharing its segment.

    ``x`` is (M, ...) stacked replicas, ``seg`` an (M,) i32 segment id per
    row.  Rows alone in their segment pass through exactly (sum of one row
    divided by 1.0).  This is the one-dispatch group averaging the batched
    sync engine and ``Algorithm.reduce_groups_stacked`` build on — a single
    segment-sum + gather instead of a Python loop over groups."""
    ones = jnp.ones((x.shape[0],), x.dtype)
    sums = jax.ops.segment_sum(x, seg, num_segments=num_segments)
    counts = jax.ops.segment_sum(ones, seg, num_segments=num_segments)
    cnt = counts[seg].reshape((-1,) + (1,) * (x.ndim - 1))
    return sums[seg] / cnt


def gossip_mix_tree(x_half, pulled, weights, *, use_pallas=None):
    """Tree-level fused mix used by the trainer and the batched simulator
    engine (x_half already includes the optimizer update, so u = 0):
    out = (1-w_i) x_half + w_i pulled, one ``mix_rows`` launch per leaf
    instead of the former per-worker-slice Python loop."""

    def one(h, p):
        return mix_rows(h, jnp.zeros_like(h), p, weights, use_pallas=use_pallas)

    return jax.tree_util.tree_map(one, x_half, pulled)
