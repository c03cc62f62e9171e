#!/usr/bin/env python3
"""Chip benchmark of NetMax training: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic,
limits and metric readers are found by name through BENCHMARK.json.  With
``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a steady
part of the window.  The last line of stdout is the result object; the
last lines of stderr are the numbers compared with the plain reference,
each beside its limit.  Exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# A fixed directory inside the checkout; the program's own cache helper
# takes it from the same variable.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    import repro.launch.train  # noqa: F401  (the system under test)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    cell = harness.resolve(args.workload, bool(args.trace))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX has {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 1
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), devices=devices[:cell.chips],
                           t_start=T_START)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
