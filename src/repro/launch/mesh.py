"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state.  The single-pod mesh is 16x16 = 256 chips (data, model);
multi-pod adds a leading pod axis: 2x16x16 = 512 chips.
"""

from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    if len(devs) == n:
        return jax.make_mesh(shape, axes, axis_types=auto)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devs)} — run under "
            "launch/dryrun.py (it forces 512 host devices) or on real hardware"
        )
    # More devices than the mesh needs (single-pod under the 512-device
    # dry-run env): build from the first n.
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape(shape), axes, axis_types=auto
    )


def make_worker_mesh(devices, tp: int = 1):
    """(data, model) mesh over ``devices`` with Auto axis types: NetMax
    workers enumerate 'data', each worker's replica spans ``tp`` devices."""
    devs = np.asarray(devices).reshape(len(devices) // tp, tp)
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.sharding.Mesh(devs, ("data", "model"), axis_types=auto)


def make_debug_mesh(n_workers: int = 2, tp: int = 1):
    """Tiny mesh for subprocess SPMD tests (host platform devices)."""
    return make_worker_mesh(jax.devices()[: n_workers * tp], tp)


def worker_count(mesh, worker_axes: tuple) -> int:
    """Number of NetMax workers enumerated by the given mesh axes."""
    M = 1
    for ax in worker_axes:
        if ax in mesh.shape:
            M *= mesh.shape[ax]
    return M


def worker_axis_names(mesh, worker_axes: tuple) -> tuple:
    """The subset of worker_axes present in this mesh (single-pod drops 'pod')."""
    return tuple(ax for ax in worker_axes if ax in mesh.shape)
