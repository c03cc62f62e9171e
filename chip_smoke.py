#!/usr/bin/env python3
"""Chip smoke test: NetMax training of qwen1.5-0.5b at its published widths.

    python3 chip_smoke.py             # one chip: M=2 replicas, 24 layers
    python3 chip_smoke.py --chips 4   # four chips, and nothing else
    python3 chip_smoke.py --seed 1    # other random weights and data (default 0)

One chip: ``repro.launch.train.train`` (the launcher's own loop) trains two
NetMax replicas of the unreduced model, gather pull and Monitor refreshes
included, for a few rounds from random weights (``--seed``) on TokenStream
data.
It checks that every round's loss is finite, that the first is within 1.0
of ln(vocab), and that every replica is finite after the last mix.

Four chips: the same loop trains four replicas, one per chip, with the
worker axis sharded over a mesh of ``jax.devices()[:4]``; the same rounds
(same data, same gossip draws) are then recomputed one replica at a time on
one chip, and ``pull_ppermute`` is compared with ``pull_gather``.  Depth is
cut to 12 layers so that the one-chip reference can hold all four replicas'
state.  Fails past bf16 tolerance.

Exits non-zero, printing no result, when the first device is not a TPU or
any check fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from collections import Counter
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.data.synthetic import TokenStream  # noqa: E402
from repro.dist import gossip  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import make_step, train  # noqa: E402
from repro.train.trainer import init_stacked  # noqa: E402

ARCH = "qwen1.5-0.5b"
ROUNDS = 6
MONITOR_EVERY = 3  # two Monitor refreshes in ROUNDS
SEQ, BATCH_PER_WORKER, LR = 128, 4, 0.02
FOUR_CHIP_LAYERS = 12
BF16_EPS = 2.0 ** -7
GIB = 2.0 ** 30


def _train(cfg, workers, devices, seed):
    return train(cfg, workers=workers, rounds=ROUNDS, devices=devices, seq=SEQ,
                 batch_per_worker=BATCH_PER_WORKER, lr=LR, seed=seed,
                 monitor_every=MONITOR_EVERY, log_every=1)


def replicas_finite(params) -> list[bool]:
    """Per replica: every element of every leaf is finite."""
    ok = None
    for leaf in jax.tree_util.tree_leaves(params):
        f = jnp.all(jnp.isfinite(leaf).reshape(leaf.shape[0], -1), axis=1)
        ok = f if ok is None else ok & f
    return [bool(x) for x in np.asarray(ok)]


def _loss_checks(run, vocab: int) -> list[str]:
    failures = []
    losses = [r.loss for r in run.rounds]
    if len(losses) < ROUNDS or not all(math.isfinite(x) for x in losses):
        failures.append(f"losses not all finite over {ROUNDS} rounds: {losses}")
    elif abs(losses[0] - math.log(vocab)) > 1.0:
        failures.append(f"round-1 loss {losses[0]:.4f} is not within 1.0 of "
                        f"ln(vocab)={math.log(vocab):.4f}")
    fin = replicas_finite(run.params)
    print(f"replicas finite after the mix: {fin}")
    if not all(fin):
        failures.append(f"non-finite replica after the mix: {fin}")
    return failures


def one_chip(cfg, device, seed: int = 0) -> list[str]:
    """M=2 NetMax replicas of ``cfg`` on ``device``; returns the failures."""
    run = _train(cfg, 2, [device], seed)
    print(f"params per worker: {run.params_per_worker}")
    print(f"compile: {run.setup['compile']:.2f}s")
    for r in run.rounds:
        wall = r.spans["dispatch"] + r.spans["device_wait"]
        print(f"round {r.round} loss={r.loss:.6f} wall={wall:.6f}s "
              f"neighbors={r.neighbors.tolist()} weights={r.weights.tolist()}")
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print("hbm peak: " + (f"{peak} bytes ({peak / GIB:.3f} GiB)" if peak is not None
                          else "not reported"))
    return _loss_checks(run, cfg.vocab_size)


def reference_rounds(cfg, logs, device, seed):
    """Replays ``logs`` (the sharded run's rounds: same data, same gossip
    draws) one replica at a time on ``device``.  Returns (per-replica params,
    per-round mean loss).  Each replica takes an M=1 local step, then mixes
    with the pre-round params of the neighbor it drew.  The step donates its
    params and optimizer state, so a replica whose pre-round params a later
    replica still pulls steps on a copy of them."""
    M = len(logs[0].neighbors)
    dev = SingleDeviceSharding(device)
    opt, step1 = make_step(cfg, 1, algo="local")  # no gossip at M=1
    mix = jax.jit(lambda h, p, w: jax.tree_util.tree_map(
        lambda a, b: a + w.astype(a.dtype) * (b - a), h, p))
    init = jax.jit(lambda k: init_stacked(cfg, opt, 1, k), out_shardings=dev)
    params, opts = map(list, zip(*(init(jax.random.PRNGKey(seed)) for _ in range(M))))
    stream = TokenStream(cfg.vocab_size, SEQ, BATCH_PER_WORKER, seed=seed)
    gi = jax.device_put({"neighbors": np.zeros(1, np.int32),
                         "weights": np.zeros(1, np.float32),
                         "lr": np.float32(LR)}, dev)
    losses = []
    for log in logs:
        need = Counter(int(j) for j in log.neighbors)  # outstanding pulls
        new, loss = [None] * M, 0.0
        for i in range(M):
            b = stream.batch(i, log.round - 1)
            batch = jax.device_put({k: b[k][None] for k in ("tokens", "labels")}, dev)
            mine = params[i]
            if need[i] > 0:  # pulled later: step on a copy
                mine = jax.tree_util.tree_map(jnp.copy, mine)
            x_half, opts[i], m = step1(mine, opts[i], batch, gi)
            j = int(log.neighbors[i])
            new[i] = mix(x_half, params[j], jnp.float32(log.weights[i]))
            loss += float(m["loss"]) / M
            need[j] -= 1
            for k in range(i + 1):  # free pre-round params nobody still pulls
                if need[k] <= 0:
                    params[k] = None
        params = new
        losses.append(loss)
    return params, losses


def four_chips(cfg, devices, seed: int = 0) -> list[str]:
    """M=4 replicas sharded one per chip, against the one-chip reference and
    ppermute against gather; returns the failures."""
    failures = []
    run = _train(cfg, 4, devices, seed)
    print(f"params per worker: {run.params_per_worker}")
    print(f"compile: {run.setup['compile']:.2f}s")
    failures += _loss_checks(run, cfg.vocab_size)

    placement = set()
    for leaf in jax.tree_util.tree_leaves(run.params):
        for s in leaf.addressable_shards:
            placement.add((s.index[0].start or 0, s.data.shape[0], s.device.id))
    print("replica -> device: " + ", ".join(
        f"{r}->{d}" for r, _, d in sorted(placement)))
    if (len(placement) != 4 or {n for _, n, _ in placement} != {1}
            or len({d for _, _, d in placement}) != 4):
        failures.append(f"not one replica per chip: {sorted(placement)}")

    perm = (1, 2, 3, 0)
    pp = jax.jit(lambda t: gossip.pull_ppermute(t, perm, run.mesh, ("data",)))
    has_cp = "collective-permute" in pp.lower(run.params).compile().as_text()
    via_pp = pp(run.params)
    via_gather = jax.jit(lambda t: gossip.pull_gather(
        t, jnp.asarray(perm, jnp.int32)))(run.params)
    d_pull = max(float(_max_diff(a, b)[0]) for a, b in zip(
        jax.tree_util.tree_leaves(via_pp), jax.tree_util.tree_leaves(via_gather)))
    del via_pp, via_gather
    print(f"ppermute vs gather, perm {perm}: max |diff| = {d_pull} "
          f"(collective-permute in HLO: {has_cp})")
    if d_pull != 0.0 or not has_cp:
        failures.append("pull_ppermute does not match pull_gather")

    run.opt_state = None  # frees the sharded optimizer state
    ref_params, ref_losses = reference_rounds(cfg, run.rounds, devices[0], seed)
    d_loss = max(abs(r.loss - x) for r, x in zip(run.rounds, ref_losses))
    flat, _ = jax.tree_util.tree_flatten_with_path(run.params)
    on0 = lambda x: jax.device_put(x, devices[0])  # noqa: E731
    worst, spread = (0.0, ""), 0.0
    for i in range(4):
        for (path, a), b in zip(flat, jax.tree_util.tree_leaves(ref_params[i])):
            diff, scale = map(float, _max_diff(on0(a[i]), b[0]))
            if diff / scale >= worst[0]:
                worst = (diff / scale, f"replica {i} {jax.tree_util.keystr(path)}: "
                                       f"|diff| {diff}, leaf max {scale}")
            if i:
                spread = max(spread, float(_max_diff(on0(a[i]), on0(a[0]))[0]) / scale)
    print(f"sharded vs one-chip reference: max |param diff| / leaf max = "
          f"{worst[0]} ({worst[1]}); replicas differ from replica 0 by up to "
          f"{spread} of leaf max; max |loss diff| = {d_loss} "
          f"(losses {[r.loss for r in run.rounds]} vs {ref_losses})")
    # Each round rounds every bf16 param twice (update, mix), and the two
    # programs accumulate in different orders, so each rounding may land one
    # ulp apart: up to 2 * ROUNDS ulps at the leaf's largest magnitude.
    if worst[0] > 2 * ROUNDS * BF16_EPS or d_loss > BF16_EPS * abs(ref_losses[0]):
        failures.append("sharded rounds differ from the one-chip reference "
                        "past bf16 tolerance")
    return failures


@jax.jit
def _max_diff(a, b):
    """(max |a - b|, max |b|) in f32, the second floored above zero."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)), jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and gossip draws")
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print("FAIL: no TPU: the smoke runs on the chip only", file=sys.stderr)
        return 1
    cfg = get_arch(ARCH)
    if args.chips == 4:
        if len(devices) < 4:
            print(f"FAIL: --chips 4 needs 4 devices, found {len(devices)}",
                  file=sys.stderr)
            return 1
        failures = four_chips(replace(cfg, n_layers=FOUR_CHIP_LAYERS), devices[:4],
                              args.seed)
    else:
        failures = one_chip(cfg, dev, args.seed)
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
