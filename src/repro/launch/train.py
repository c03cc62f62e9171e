"""Training driver: ``python -m repro.launch.train --arch <id> ...``

Composes the full stack: arch config -> worker mesh -> NetMax trainer (or a
baseline algorithm) -> Network Monitor -> checkpoint/restart.  ``train`` is
the one training loop: ``main`` calls it from the command line and
``chip_smoke.py`` calls it at full width on a TPU.  Only ``--reduced``
shrinks a config, except that a CPU backend always trains the reduced config
(tests), and says so.

Given more than one device, ``train`` builds a (data, model) mesh over them
and shards the stacked worker axis over 'data'; ``main`` gives it the largest
device count that divides the worker count.  The same step function the
multi-pod dry-run lowers is executed here — there is exactly one trainer
code path.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import math
import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.algos import get_algorithm
from repro.configs.base import ArchConfig, get_arch
from repro.core import consensus
from repro.core.monitor import IterationTimeEMA, NetworkMonitor
from repro.core.nettime import LinkTimeModel, Topology
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_worker_mesh
from repro.optim import sgd
from repro.train import checkpoint as ckpt
from repro.train.trainer import TrainStepConfig, init_stacked, make_train_step


@dataclass
class RoundLog:
    round: int  # 1-based
    loss: float
    # host seconds of each phase that ran this round (``_span`` names:
    # stage, draw, dispatch, device_wait, readback, ema, log; monitor and
    # checkpoint on the rounds that refresh or save), and of what interrupted
    # them (``NESTED``: gc, backend_compile) when any did
    spans: dict[str, float]
    neighbors: np.ndarray  # (M,) i32, the round's gossip draw
    weights: np.ndarray  # (M,) f32


@dataclass
class Refresh:
    """One Network Monitor refresh of P."""
    round: int
    ms: float  # host time of collect + step + the repair of P
    n_solves: int  # simplex runs of the sweep
    n_pivots: int
    n_warm_used: int
    applied: bool  # the LP gave a policy and P changed


@dataclass
class TrainRun:
    params: object  # stacked (M, ...) leaves, on the run's devices
    opt_state: object
    mesh: object  # None on one device
    params_per_worker: int
    setup: dict[str, float] = field(default_factory=dict)  # init, compile seconds
    compiles: int = 0  # backend compiles while train() ran, of any program
    gc: dict[int, tuple[int, float]] = field(default_factory=dict)  # gen -> (count, s)
    refreshes: list[Refresh] = field(default_factory=list)
    rounds: list[RoundLog] = field(default_factory=list)


@contextlib.contextmanager
def _span(name: str, rec: dict):
    """A host span: a profiler annotation, on the device trace's clock when a
    trace is being taken (a cheap check when none is), whose seconds are
    added to ``rec[name]``."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        rec[name] = rec.get(name, 0.0) + time.perf_counter() - t0


# Seconds that interrupt a phase rather than follow it: they overlap the
# phase they fell in, so a round's length is the sum of its other spans.
NESTED = ("gc", "backend_compile")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Watch:
    """While entered: each collection of Python's collector is a ``gc``
    span, counted in ``gc`` = {generation: (collections, seconds)}; each
    backend compile is counted in ``compiles``.  Both add their seconds to
    ``rec`` (the record of the round or set-up then running) under ``gc``
    and ``backend_compile``."""

    def __init__(self, rec: dict):
        self.rec = rec
        self.gc: dict[int, tuple[int, float]] = {}
        self.compiles = 0
        self._open: list = []

    def __enter__(self):
        gc.callbacks.append(self._collection)
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._collection)
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _add(self, key: str, secs: float):
        self.rec[key] = self.rec.get(key, 0.0) + secs

    def _collection(self, phase, info):
        if phase == "start":
            ann = jax.profiler.TraceAnnotation("gc")
            ann.__enter__()
            self._open.append((ann, time.perf_counter()))
        elif self._open:
            ann, t0 = self._open.pop()
            ann.__exit__(None, None, None)
            secs = time.perf_counter() - t0
            n, total = self.gc.get(info["generation"], (0, 0.0))
            self.gc[info["generation"]] = (n + 1, total + secs)
            self._add("gc", secs)

    def _event(self, event: str, secs: float, **kw):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self._add("backend_compile", secs)


def _slowest(rounds: list[RoundLog]) -> str:
    """The five longest rounds, each with every span it recorded (ms)."""
    def length(g):
        return sum(v for n, v in g.spans.items() if n not in NESTED)
    return "; ".join(
        f"{g.round} {length(g) * 1e3:.2f}ms: " + " ".join(
            f"{n}={v * 1e3:.2f}" for n, v in g.spans.items())
        for g in sorted(rounds, key=length, reverse=True)[:5])


def make_step(cfg: ArchConfig, M: int, *, algo: str = "netmax",
              gossip: str = "gather", mesh=None):
    """(optimizer, jitted round) for M stacked workers.  The round donates
    its params and optimizer state: the new state is written over the old,
    whose arrays the call deletes.  Undonated, the outputs hold a second
    copy of the state and XLA, short of HBM, recomputes the tied 152k-vocab
    head three more times a microbatch; donated, it recomputes nothing and
    copies nothing for the pull (v5e compile of qwen1.5-0.5b in f32, 16
    layers, M=2, 2 x 512 tokens a worker: 13.60 GiB and 14 ``.remat``
    instructions undonated, 12.65 GiB and none donated)."""
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    if algo == "prague":
        algorithm = get_algorithm("prague", trainer_groups=max(2, M // 2))
    else:
        algorithm = get_algorithm("netmax" if algo == "local" else algo)
    step_cfg = TrainStepConfig(
        gossip_mode="none" if algo in ("allreduce", "local") else gossip,
    )
    worker_axes = ("data",) if mesh is not None else ()
    step = make_train_step(cfg, opt, M, algorithm, step_cfg, mesh=mesh,
                           worker_axes=worker_axes)
    return opt, jax.jit(step, donate_argnums=(0, 1))


def train(
    cfg: ArchConfig,
    *,
    workers: int,
    rounds: int,
    devices=None,
    seq: int = 128,
    batch_per_worker: int = 4,
    lr: float = 0.02,
    algo: str = "netmax",
    gossip: str = "gather",
    seed: int = 0,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    monitor_every: int = 10,
    log_every: int = 10,
) -> TrainRun:
    """Train ``workers`` NetMax replicas of ``cfg`` for ``rounds`` rounds on
    ``devices`` (default: the first device).  Every round ends in
    ``block_until_ready``; its loss, gossip draw and the host seconds of its
    phases are kept.  Each round runs under a ``round`` step annotation and
    each phase under a span of its name (``_span``), so a profiler trace
    taken meanwhile shows them on the device's clock; Python's collections
    show as ``gc`` spans.  The run's output ends with its slowest rounds and
    their spans."""
    M = workers
    devices = list(devices) if devices is not None else jax.devices()[:1]
    if M % len(devices):
        raise ValueError(f"{M} workers do not divide over {len(devices)} devices")
    if len(devices) > 1:
        mesh = make_worker_mesh(devices)
        state_sh = NamedSharding(mesh, PartitionSpec("data"))
        repl_sh = NamedSharding(mesh, PartitionSpec())
    else:
        mesh = None
        state_sh = repl_sh = SingleDeviceSharding(devices[0])

    opt, step_fn = make_step(cfg, M, algo=algo, gossip=gossip, mesh=mesh)
    stream = TokenStream(cfg.vocab_size, seq, batch_per_worker, seed=seed)

    topo = Topology(M, workers_per_host=max(1, M // 2), hosts_per_pod=1)
    link = LinkTimeModel(topo, jitter=0.05, seed=1)
    monitor = NetworkMonitor(M, alpha=lr, K=6, R=6)
    emas = [IterationTimeEMA(M, beta=0.5) for _ in range(M)]
    d = np.ones((M, M)) - np.eye(M)
    P = np.where(d > 0, 1.0 / max(M - 1, 1), 0.0)
    rho = 0.5 / (2 * lr * max(M - 1, 1))
    rng = np.random.default_rng(seed)

    setup: dict[str, float] = {}
    with _Watch(setup) as watch:
        start = 0
        with _span("init", setup):
            init = jax.jit(lambda k: init_stacked(cfg, opt, M, k),
                           out_shardings=state_sh)
            params, opt_state = init(jax.random.PRNGKey(seed))
            if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
                params, opt_state, man, mon = ckpt.restore(ckpt_dir, params, opt_state)
                params, opt_state = jax.device_put((params, opt_state), state_sh)
                start = man["data_cursor"].get("round", 0)
                if mon and "P" in mon:
                    P, rho = np.asarray(mon["P"]), mon.get("rho", rho)
                print(f"[resume] round {start}")

        n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)) // M
        run = TrainRun(params=None, opt_state=None, mesh=mesh, params_per_worker=n,
                       setup=setup)
        print(f"[{algo}] arch={cfg.name} M={M} params/worker={n/1e6:.1f}M "
              f"gossip={gossip} devices={len(devices)} batch/worker={batch_per_worker}x{seq}")

        compiled = None
        t_virt = 0.0
        for r in range(start, rounds):
            # One round, and under it the phases of the loop body, back to back.
            rec = watch.rec = {}
            with jax.profiler.StepTraceAnnotation("round", step_num=r + 1):
                with _span("stage", rec):
                    batch = jax.device_put(
                        {k: np.stack([stream.batch(w, r)[k] for w in range(M)])
                         for k in ("tokens", "labels")},
                        state_sh,
                    )
                with _span("draw", rec):
                    nb, wts = consensus.sample_round(rng, P, lr, rho, d)
                    gi = jax.device_put({"neighbors": nb, "weights": wts,
                                         "lr": np.float32(lr)}, repl_sh)
                if compiled is None:
                    watch.rec = setup
                    with _span("compile", setup):
                        compiled = step_fn.lower(params, opt_state, batch, gi).compile()
                    watch.rec = rec
                    print(f"compiled round program in {setup['compile']:.2f}s")
                with _span("dispatch", rec):
                    params, opt_state, m = compiled(params, opt_state, batch, gi)
                with _span("device_wait", rec):
                    jax.block_until_ready((params, opt_state, m))
                with _span("readback", rec):
                    loss = float(m["loss"])
                    run.rounds.append(RoundLog(r + 1, loss, rec, nb, wts))
                with _span("ema", rec):
                    for i in range(M):
                        emas[i].update(int(nb[i]),
                                       link.iteration_time(i, int(nb[i]), now=t_virt))
                    t_virt += max(link.iteration_time(i, int(nb[i]), now=t_virt)
                                  for i in range(M))

                if algo == "netmax" and (r + 1) % monitor_every == 0:
                    with _span("monitor", rec):
                        monitor.collect({i: emas[i].snapshot() for i in range(M)})
                        pol = monitor.step()
                        if pol.ok:
                            P, rho = pol.P, pol.rho
                            bad = P.sum(axis=1) <= 0
                            P[bad] = np.where(d[bad] > 0, 1.0 / max(M - 1, 1), 0.0)
                        print(f"  [monitor] round {r+1}: lambda2={pol.lambda2:.4f} "
                              f"rho={rho:.4f} solves={pol.n_solves} "
                              f"pivots={pol.n_pivots} warm={pol.n_warm_used} "
                              f"applied={bool(pol.ok)}")
                    run.refreshes.append(Refresh(
                        r + 1, rec["monitor"] * 1e3, pol.n_solves, pol.n_pivots,
                        pol.n_warm_used, bool(pol.ok)))
                with _span("log", rec):
                    if (r + 1) % log_every == 0 or r == start:
                        step_wall = rec["dispatch"] + rec["device_wait"]
                        print(f"round {r+1:5d} loss={loss:.4f} step_wall={step_wall:.4f}s "
                              f"virt={t_virt:.1f}s")
                if ckpt_dir and (r + 1) % ckpt_every == 0:
                    with _span("checkpoint", rec):
                        ckpt.save(ckpt_dir, r + 1, params, opt_state,
                                  monitor_state={"rho": float(rho), "P": P.tolist()},
                                  data_cursor={"round": r + 1})
                        print(f"  [checkpoint] saved round {r+1}")

    run.gc, run.compiles = watch.gc, watch.compiles
    # Where the slowest rounds went, in the run in which they happened.
    print(f"slowest rounds: {_slowest(run.rounds)}")
    run.params, run.opt_state = params, opt_state
    return run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (always on a cpu backend)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--algo", default="netmax",
                    choices=["netmax", "allreduce", "prague", "local"])
    ap.add_argument("--gossip", default="gather", choices=["gather", "masked_psum"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--monitor-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced or jax.default_backend() == "cpu":
        if not args.reduced:
            print(f"[train] {jax.default_backend()} backend: training the "
                  f"reduced {cfg.name} config")
        cfg = cfg.reduced()
    devices = jax.devices()
    train(
        cfg, workers=args.workers, rounds=args.rounds,
        devices=devices[: math.gcd(args.workers, len(devices))],
        seq=args.seq, batch_per_worker=args.batch_per_worker, lr=args.lr,
        algo=args.algo, gossip=args.gossip, ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every, monitor_every=args.monitor_every,
        log_every=args.log_every,
    )
    print("done.")


if __name__ == "__main__":
    main()
