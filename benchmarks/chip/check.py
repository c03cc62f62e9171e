"""The numbers that decide ``correct``: the program's first three rounds
against the plain reference's, from the same weights, rows and draws.

- ``loss_gap``: the largest gap over the three rounds between the program's
  mean loss and the reference's (nats).
- ``grad_gap``: for each worker and leaf, the gap between the norms of the
  momentum after round 1 (the first gradient as the optimizer takes it,
  weight decay included), over the reference's norm of that leaf or of the
  median leaf, whichever is larger; the worst leaf.
- ``change_gap``: the same for the change of the weights over the three
  rounds, on the leaves whose first reference gradient is at least
  ``MOVED`` of the median leaf's (the others, such as a key bias under
  softmax, move by round-off alone).
"""

from __future__ import annotations

import math

import numpy as np

MOVED = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def _worst(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray | None = None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / floor))


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """``prog``: loss (3,), mom1 (M, leaves), change (M, leaves);
    ``ref``: the same and grad1 (M, leaves)."""
    g = np.asarray(ref["grad1"])
    keep = g >= MOVED * np.median(g)
    return {
        "loss_gap": float(np.max(np.abs(np.subtract(prog["loss"], ref["loss"])))),
        "grad_gap": _worst(prog["mom1"], ref["mom1"]),
        "change_gap": _worst(prog["change"], ref["change"], keep),
    }


def verdict(nums: dict[str, float], limits: dict[str, float]) -> bool:
    """Every number finite, and every number that has a limit within it.  A
    cell's limits file leaves out a number that no control or fault
    separates from sound runs (see PERF.md)."""
    return all(math.isfinite(nums[k]) and nums[k] <= limits.get(k, math.inf)
               for k in NUMBERS)
