#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 12 \
        --control-seeds 3 [--first-seed N] [--out FILE]

For each seed, in one process on the chip: the program's first three rounds
through the launcher's loop (the same stand-ins as a run, no window), the
plain float32 reference, and the numbers of ``check.py`` between them.  On
the first ``--control-seeds`` seeds also the control (the reference one
precision below the configuration's, in the program's place) and two faults planted in the reference in
the program's place: half of every batch left out (the mean over the
rest), and the pull left out (no worker mixes).  A step that returns its
state unchanged reads ``change_gap`` = 1 and needs no run.  Prints one JSON
line per seed and source, and writes them to ``--out``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402
from tokens import token_pool  # noqa: E402


def leaf_names(dims) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(
        weights.shapes(dims), is_leaf=lambda x: isinstance(x, tuple))
    return [jax.tree_util.keystr(p) for p, _ in flat]


def program_rounds(cell, seed, devices):
    """The program's first rounds from ``seed``: (prog numbers' inputs,
    draws, pool)."""
    conf, tr = cell.config, cell.traffic
    dims = weights.Dims.from_config(conf)
    arch = harness.program_config(conf)
    M, B, S = tr["workers"], tr["batch_per_worker"], tr["seq_len"]
    pool = token_pool(seed, dims.vocab, M, tr["pool_batches"], B, S)
    hooks = harness.Hooks(pool, dims, jax.numpy.dtype(arch.dtype), seed, tr["monitor_every"])
    hooks.reset(probe=True)
    with harness.stand_ins(hooks) as train, contextlib.redirect_stdout(sys.stderr):
        run = train(arch, rounds=harness.CHECKED_ROUNDS, workers=M, devices=devices,
                    seq=S, batch_per_worker=B, lr=tr["lr"], algo=tr["algo"],
                    gossip=tr["gossip"], seed=seed, monitor_every=tr["monitor_every"],
                    log_every=tr["log_every"])
    prog = {"loss": [r.loss for r in run.rounds], "mom1": hooks.mom1, "change": hooks.change}
    draws = [(r.neighbors, r.weights) for r in run.rounds]
    return prog, draws, pool, hooks


def replay(cell, hooks, pool, draws, devices, **kw):
    tr = cell.traffic
    p0 = jax.jit(weights.init, static_argnums=(0, 2))(hooks.dims, hooks.key, hooks.dtype)
    return reference.rounds(
        hooks.dims, p0, [pool[:, r % pool.shape[1]] for r in range(harness.CHECKED_ROUNDS)],
        draws, lr=tr["lr"], momentum=tr["momentum"], weight_decay=tr["weight_decay"],
        devices=devices, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dtype", default=None,
                    help="run the program's path in this dtype instead of the file's")
    args = ap.parse_args(argv)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.resolve(args.workload, False)
    if args.dtype:
        cell.config["program"]["set"]["dtype"] = args.dtype
    devices = jax.devices()[: cell.chips]
    out = open(args.out, "a") if args.out else None
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        prog, draws, pool, hooks = program_rounds(cell, seed, devices)
        ref = replay(cell, hooks, pool, draws, devices)
        lines = [("program", prog)]
        if k < args.control_seeds:
            B = cell.traffic["batch_per_worker"]
            half = (replay(cell, hooks, pool, draws, devices, rows=B // 2) if B > 1 else
                    None)
            nopull = [(nb, np.zeros_like(w)) for nb, w in draws]
            quant = reference.CONTROL[str(hooks.dtype)]
            lines += [(f"control_{quant}", replay(cell, hooks, pool, draws, devices, quant=quant)),
                      ("fault_no_pull", replay(cell, hooks, pool, nopull, devices))]
            if half is not None:
                lines.append(("fault_half_batch", half))
        names = leaf_names(hooks.dims)
        for source, got in lines:
            moved = np.asarray(ref["change"]) > 0
            unmoved = sorted({names[j] for i, j in zip(*np.nonzero(
                (np.asarray(got["change"]) == 0) & moved))})
            rec = {"cell": cell.name, "seed": seed, "source": source,
                   "dtype": str(hooks.dtype), **check.numbers(got, ref),
                   "unmoved_leaves": unmoved,
                   "loss_prog": list(map(float, got["loss"])),
                   "loss_ref": list(map(float, ref["loss"]))}
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
