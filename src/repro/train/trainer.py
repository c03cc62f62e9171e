"""NetMax training step, SPMD-ready, driven by a pluggable ``Algorithm``.

``make_train_step`` builds the jit-able per-round function.  Parameters are
*stacked* over NetMax workers (leading M dim, sharded over the worker mesh
axes); one round = every worker performs one Alg.-2 iteration:

  1. per-worker grads               (vmapped value_and_grad)
  2. algorithm grad reduction       (identity | all-mean | group-mean)
  3. local optimizer step           (x_half; momenta stay worker-local)
  4. gossip pull of pre-round x     (gather | ppermute | compressed)
  5. algorithm consensus mix        (the same leaf rule the event-driven
                                     simulator applies — DESIGN.md §1)

Steps 1, 2–3, 4 and 5 run under the named scopes ``forward_backward``,
``optimizer``, ``gossip_pull`` and ``gossip_mix``: they change only the
``op_name`` metadata of the compiled program, so a profiler trace can give
each op's device time to its step.

The strategy (which peers, which weights, which reduction) comes from
``repro.algos``: pass an ``Algorithm`` instance or a registry name.  The
pre-protocol boolean flags on ``TrainStepConfig`` (``allreduce``,
``prague_groups``) still work as a deprecation shim that maps them onto
registry names; ``gossip_mode`` / ``use_gossip_mix_kernel`` / ``grad_clip``
remain *execution* options orthogonal to the strategy.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.algos import Algorithm, get_algorithm
from repro.configs.base import ArchConfig
from repro.dist import gossip
from repro.models import lm
from repro.optim import Optimizer


@dataclass(frozen=True)
class TrainStepConfig:
    gossip_mode: str = "gather"  # gather | ppermute | masked_psum | none
    allreduce: bool = False  # DEPRECATED: use algo="allreduce"
    prague_groups: int = 0  # DEPRECATED: use algo="prague"
    use_gossip_mix_kernel: bool = False  # Pallas fused mix (TPU)
    grad_clip: float = 0.0


def resolve_algorithm(algo, step_cfg: TrainStepConfig) -> Algorithm:
    """Map the caller's strategy spec (Algorithm | name | legacy flags) to an
    Algorithm instance."""
    if algo is not None and (step_cfg.allreduce or step_cfg.prague_groups > 1):
        raise ValueError(
            "conflicting strategy specs: an explicit algo was given alongside "
            "legacy TrainStepConfig flags (allreduce/prague_groups); drop the "
            "flags"
        )
    if isinstance(algo, Algorithm):
        return algo
    if isinstance(algo, str):
        return get_algorithm(algo)
    # Legacy: derive the strategy from TrainStepConfig booleans.
    if step_cfg.allreduce:
        warnings.warn(
            "TrainStepConfig(allreduce=True) is deprecated; pass "
            "algo='allreduce' to make_train_step instead",
            DeprecationWarning, stacklevel=3,
        )
        return get_algorithm("allreduce")
    if step_cfg.prague_groups > 1:
        warnings.warn(
            "TrainStepConfig(prague_groups=...) is deprecated; pass "
            "algo='prague' to make_train_step instead",
            DeprecationWarning, stacklevel=3,
        )
        return get_algorithm("prague", trainer_groups=step_cfg.prague_groups)
    # Default gossip strategy: the mixing weights arrive per-round via
    # gossip_in, so netmax covers the whole adaptive/uniform gossip family.
    return get_algorithm("netmax")


def make_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    M: int,
    algo: Algorithm | str | TrainStepConfig | None = None,
    step_cfg: TrainStepConfig | None = None,
    mesh=None,
    worker_axes: tuple = (),
    param_specs=None,
):
    """Returns train_step(params, opt_state, batch, gossip_in) ->
    (params, opt_state, metrics).

    params/opt_state leaves: (M, ...).  batch leaves: (M, B/M, ...).
    gossip_in: {'neighbors': (M,) i32, 'weights': (M,) f32, 'lr': f32[],
                'perm': static via closure for ppermute mode}

    ``algo``: an Algorithm instance or registry name.  Passing a
    TrainStepConfig here (the pre-registry calling convention) still works:
    its flags select the strategy via the deprecation shim.
    """
    if isinstance(algo, TrainStepConfig):
        assert step_cfg is None, "pass TrainStepConfig once, not twice"
        step_cfg = algo
        algo = None
    if step_cfg is None:
        step_cfg = TrainStepConfig()
    algorithm = resolve_algorithm(algo, step_cfg)
    if not algorithm.supports_trainer:
        raise NotImplementedError(
            f"algorithm {algorithm.name!r} has no lockstep SPMD form; "
            "use the event-driven simulator (train/simulator.py) instead"
        )

    def per_worker_loss(p, b):
        return lm.loss_fn(p, b, cfg)

    vgrad = jax.vmap(jax.value_and_grad(per_worker_loss))

    def grad_fn(params, batch):
        from repro.models.scan_utils import microbatch_scan

        return microbatch_scan(vgrad, params, batch, cfg.microbatches)

    def local_step(params, opt_state, batch, lr):
        with jax.named_scope("forward_backward"):
            losses, grads = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            if step_cfg.grad_clip:
                from repro.optim.optimizers import clip_by_global_norm

                grads, _ = clip_by_global_norm(grads, step_cfg.grad_clip)
            # Strategy-owned grad reduction: identity for gossip, global mean
            # for allreduce/ps-sync, group mean for prague.
            grads = algorithm.transform_grads(grads, M)
            updates, opt_state = optimizer.update(grads, opt_state, params, lr)
            x_half = optimizer.apply(params, updates)
        return losses, x_half, opt_state

    def gossip_pull(params, neighbors, perm):
        if step_cfg.gossip_mode == "gather":
            return gossip.pull_gather(params, neighbors)
        if step_cfg.gossip_mode == "masked_psum":
            return gossip.pull_masked_psum(params, neighbors, M)
        if step_cfg.gossip_mode == "ppermute":
            assert perm is not None and mesh is not None
            return gossip.pull_ppermute(params, perm, mesh, worker_axes, specs=param_specs)
        raise ValueError(step_cfg.gossip_mode)

    communicates = (
        algorithm.communicates_in_trainer
        and step_cfg.gossip_mode != "none"
        and M > 1
    )

    def train_step(params, opt_state, batch, gossip_in, *, perm=None):
        lr = gossip_in["lr"]
        losses, x_half, opt_state = local_step(params, opt_state, batch, lr)
        if communicates:
            with jax.named_scope("gossip_pull"):
                pulled = gossip_pull(params, gossip_in["neighbors"], perm)
            with jax.named_scope("gossip_mix"):
                if step_cfg.use_gossip_mix_kernel and type(algorithm).delta_transform is Algorithm.delta_transform:
                    from repro.kernels import ops as kops

                    # Fused Pallas mix — only valid for the identity delta
                    # transform (the kernel hard-codes the linear mix).
                    new_params = kops.gossip_mix_tree(
                        x_half, pulled, gossip_in["weights"]
                    )
                else:
                    new_params = algorithm.mix_stacked(
                        x_half, pulled, gossip_in["weights"]
                    )
        else:
            new_params = x_half
        metrics = {"loss": losses.mean(), "loss_per_worker": losses}
        return new_params, opt_state, metrics

    return train_step


def init_stacked(cfg: ArchConfig, optimizer: Optimizer, M: int, key):
    """Initialize M worker replicas (identical start — paper Alg. 2 line 1
    uses independent x_i^0; identical init is the common practical choice
    and also what D-PSGD baselines use)."""
    params1 = lm.init_params(cfg, key)
    params = jax.tree_util.tree_map(lambda l: jnp.broadcast_to(l[None], (M,) + l.shape), params1)
    # Materialize (broadcast_to creates views; optimizer needs real buffers).
    params = jax.tree_util.tree_map(jnp.array, params)
    opt_state = optimizer.init(params)
    return params, opt_state


def abstract_stacked(cfg: ArchConfig, optimizer: Optimizer, M: int):
    """ShapeDtypeStructs for the stacked training state (dry-run)."""
    p1 = lm.abstract_params(cfg)
    stack = lambda l: jax.ShapeDtypeStruct((M,) + l.shape, l.dtype)
    params = jax.tree_util.tree_map(stack, p1)
    opt_state = jax.eval_shape(optimizer.init, params)
    return params, opt_state
