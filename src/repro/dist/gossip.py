"""Gossip pull lowerings + consensus mix on stacked replicas.

Three equivalent lowerings of "worker i pulls the pre-round params of
neighbor m_i" over leaves stacked (M, ...) on the worker mesh axes:

  pull_gather       jnp.take along the worker dim — XLA lowers the cross-
                    shard gather to all-gather + dynamic-slice.  Simplest;
                    moves O(M) params per worker in the worst case.
  pull_masked_psum  one-hot matmul along the worker dim — lowers to a
                    masked all-reduce; same wire cost as an all-reduce but
                    a single fused collective.
  pull_ppermute     shard_map + lax.ppermute — a true point-to-point
                    collective-permute, O(1) params per link, but only
                    valid when the neighbor draw is a permutation (the
                    host-side sampler can always re-draw into one).

All three agree numerically (tests/test_spmd.py); the dry-run harness
compares their lowered collective bytes per DESIGN.md §6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pull_gather(params, neighbors):
    """pulled[i] = params[neighbors[i]] via take along the stacked dim."""
    return jax.tree_util.tree_map(
        lambda x: jnp.take(x, neighbors, axis=0), params
    )


def pull_masked_psum(params, neighbors, M: int):
    """One-hot contraction over the worker dim (lowers to a masked psum)."""
    oh = jax.nn.one_hot(neighbors, M)

    def leaf(x):
        sel = jnp.einsum("ij,j...->i...", oh.astype(x.dtype), x)
        return sel.astype(x.dtype)

    return jax.tree_util.tree_map(leaf, params)


def pull_ppermute(params, perm, mesh, worker_axes, specs=None):
    """Point-to-point pull for permutation draws: device i receives the
    replica of device perm[i] via lax.ppermute over the worker mesh axes.

    ``perm``: tuple of source indices (pulled[i] = params[perm[i]]).
    ``specs``: optional PartitionSpec tree for the params (defaults to
    leading-axis sharding over ``worker_axes``, everything else replicated).
    """
    axes = tuple(worker_axes)
    if not axes:
        return pull_gather(params, jnp.asarray(perm, dtype=jnp.int32))
    axis_name = axes if len(axes) > 1 else axes[0]
    # ppermute pairs are (source_device, destination_device): destination i
    # receives from source perm[i].
    pairs = [(int(perm[i]), i) for i in range(len(perm))]

    if specs is None:
        specs = jax.tree_util.tree_map(
            lambda x: P(axes, *([None] * (x.ndim - 1))), params
        )

    def inner(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name=axis_name, perm=pairs), tree
        )

    return jax.shard_map(
        inner, mesh=mesh, in_specs=(specs,), out_specs=specs,
    )(params)


def mix(x_half, pulled, weights):
    """Consensus mix on stacked replicas (Alg. 2 lines 13-15):
    out_i = (1 - w_i) * x_half_i + w_i * pulled_i."""

    def leaf(h, p):
        w = weights.reshape((-1,) + (1,) * (h.ndim - 1)).astype(h.dtype)
        return (1.0 - w) * h + w * p

    return jax.tree_util.tree_map(leaf, x_half, pulled)
