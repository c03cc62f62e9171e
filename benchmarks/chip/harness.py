"""One run of one cell: set-up, the measured window, the trace, the check.

The window drives ``repro.launch.train.train``, the program's one training
loop, with every round's host work.  The harness changes no program file;
for the length of a run it puts three stand-ins into that module's
namespace:

- ``TokenStream``: serves the cell's token pool (``tokens.py``) and stamps
  the start of every round on the host clock (the loop asks it for round
  r's batch first thing in round r);
- ``init_stacked``: starts every replica from the benchmark's weights
  (the model module's ``init``), drawn on the device in the loop's own
  jitted call;
- ``make_step``: wraps the compiled round so that, after rounds 1 and 3,
  the leaf norms of the state are read for the check, and in a traced
  run the compiled round's text is kept for the scopes of its ops.

The model is the module ``models/<model_type>.py``, found by the
configuration file's own ``model_type`` as a metric's reader is found by its
name: its sizes (``Dims``), weights (``shapes``, ``init``), plain forward
pass (``seq_loss``), model FLOPs and the program's config fields that have
to match (``program_keys``).  Nothing here names a model.

The loop takes a round count, so a short call in set-up measures the round
time and sets the count; the window then holds the rounds of a second call
from ``WINDOW_FIRST`` on, after the compile and the checked rounds, and
ends when ``train`` returns.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference
import spans
import xplane as trace_mod
from flops import round_flops
from peaks import peaks
from tokens import token_pool

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CAL_ROUNDS = 3      # the calibration call: a compile, then two timed rounds
CHECKED_ROUNDS = 3  # rounds the reference follows
WINDOW_FIRST = 4    # first round of the window call that is measured
TRACE_SKIP = 2      # traced rounds start this many rounds into the window


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: list  # the metric entries this run reports, in BENCHMARK.json order


def _for_cell(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve(name: str, trace: bool, bench: dict | None = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files, found by name."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    metrics = _for_cell(bench["per_layer" if trace else "end_to_end"], name)
    for m in metrics:
        if not (HERE / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"no reader metrics/{m['name']}.py")
    config = json.loads((ROOT / conf["file"]).read_text())
    model_of(config)
    return Cell(
        name=name, chips=w["chips"], config=config,
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((HERE / "limits" / f"{name}.json").read_text()),
        metrics=metrics,
    )


def _load(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark's directory, loaded
    once a process (a model module is a static argument of jitted calls)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind}/{name}.py in {HERE}")
    key = f"chip_{kind}_{name}"
    mod = sys.modules.get(key)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def model_of(conf: dict):
    """The module that describes a configuration file's model: the file
    ``models/<model_type>.py``, by the published ``model_type`` key."""
    return _load("models", conf["model_type"])


def _field(cfg, key: str):
    for part in key.split("."):
        cfg = getattr(cfg, part)
    return cfg


def program_config(conf: dict):
    """The program's ArchConfig for a configuration file, checked against
    the file's published keys: every field that the model module's
    ``program_keys`` names (``moe.top_k`` names a field of a nested
    config).  In the file's ``program.set`` a nested config is given as a
    dict of the fields that change."""
    from repro.configs.base import get_arch

    base = get_arch(conf["program"]["registered"])
    cfg = dataclasses.replace(base, **{
        k: dataclasses.replace(getattr(base, k), **v) if isinstance(v, dict) else v
        for k, v in conf["program"]["set"].items()})
    model = model_of(conf)
    want = model.program_keys(model.Dims.from_config(conf))
    bad = {k: (_field(cfg, k), v) for k, v in want.items() if _field(cfg, k) != v}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def _reader(name: str):
    return _load("metrics", name).read


@jax.jit
def _replica_norms(tree):
    """(M, leaves) norms of each replica of each stacked leaf."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                         axis=tuple(range(1, x.ndim))))
        for x in jax.tree_util.tree_leaves(tree)], axis=1)


class Hooks:
    """The stand-ins' shared state for one ``train`` call."""

    def __init__(self, pool, model, dims, dtype, seed):
        self.pool, self.model, self.dims, self.dtype = pool, model, dims, dtype
        self.key = jax.random.PRNGKey(seed)
        self.reset()

    def reset(self, probe=False, trace_rounds=None, trace_dir=None):
        self.stamps: dict[int, float] = {}
        self.probe, self.calls = probe, 0
        self.mom1 = self.change = None
        self.trace_rounds, self.trace_dir = trace_rounds, trace_dir
        self.text = None  # the compiled round's text, in a traced call

    # -- TokenStream ------------------------------------------------------
    def stream(self, vocab_size, seq_len, batch_size, seed=0):
        W, K, B, S1 = self.pool.shape
        if (seq_len, batch_size) != (S1 - 1, B):
            raise ValueError("the loop asked for other batches than the cell's")
        return self

    def batch(self, worker: int, step: int) -> dict:
        if worker == 0 and step not in self.stamps:
            self._round_start(step)
        rows = self.pool[worker, step % self.pool.shape[1]]
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    def _round_start(self, r):
        tr = self.trace_rounds
        if tr is not None and r == tr[0]:
            jax.profiler.start_trace(self.trace_dir)
        if tr is not None and tr[0] <= r <= tr[1]:
            with jax.profiler.TraceAnnotation(f"{trace_mod.MARK}.{r}"):
                pass
        if tr is not None and r == tr[1]:
            jax.profiler.stop_trace()
        self.stamps[r] = time.perf_counter()

    # -- init_stacked -----------------------------------------------------
    def init_stacked(self, cfg, optimizer, M, key):
        p1 = self.model.init(self.dims, key, self.dtype)
        params = jax.tree_util.tree_map(
            lambda x: jnp.array(jnp.broadcast_to(x[None], (M,) + x.shape)), p1)
        return params, optimizer.init(params)

    # -- make_step --------------------------------------------------------
    def wrap_step(self, make_step):
        hooks = self

        class Compiled:
            def __init__(self, c):
                self.c = c

            def __call__(self, params, opt_state, batch, gi):
                out = self.c(params, opt_state, batch, gi)
                if hooks.probe and hooks.calls < CHECKED_ROUNDS:
                    hooks.after_round(out)
                return out

        class Lowered:
            def __init__(self, low):
                self.low = low

            def compile(self):
                c = self.low.compile()
                if hooks.trace_rounds is not None:
                    hooks.text = c.as_text()
                return Compiled(c)

        class Jitted:
            def __init__(self, fn):
                self.fn = fn

            def lower(self, *args):
                return Lowered(self.fn.lower(*args))

        def make(*args, **kw):
            opt, fn = make_step(*args, **kw)
            return opt, Jitted(fn)

        return make

    def after_round(self, out):
        self.calls += 1
        params, opt_state, _ = out
        if self.calls == 1:
            self.mom1 = np.asarray(_replica_norms(opt_state["m"]))
        if self.calls == CHECKED_ROUNDS:
            self.change = np.asarray(_change_norms(
                params, self.key, self.model, self.dims, self.dtype))


@partial(jax.jit, static_argnums=(2, 3, 4))
def _change_norms(params, key, model, dims, dtype):
    """(M, leaves) norms of each replica's change from the initial weights,
    which are drawn again from ``key`` inside the program."""
    p0 = model.init(dims, key, dtype)
    return _replica_norms(jax.tree_util.tree_map(
        lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32)[None], params, p0))


@contextlib.contextmanager
def stand_ins(hooks: Hooks):
    import repro.launch.train as loop

    saved = {k: getattr(loop, k) for k in ("TokenStream", "init_stacked", "make_step")}
    loop.TokenStream = hooks.stream
    loop.init_stacked = hooks.init_stacked
    loop.make_step = hooks.wrap_step(saved["make_step"])
    try:
        yield loop.train
    finally:
        for k, v in saved.items():
            setattr(loop, k, v)


class CompileCounter:
    """Backend compiles, with the host time at which each ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.at.append(time.perf_counter())


def memory_peak(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             t_start: float, log=sys.stderr) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    conf, tr = cell.config, cell.traffic
    model = model_of(conf)
    dims = model.Dims.from_config(conf)
    arch = program_config(conf)
    dtype = jnp.dtype(arch.dtype)
    M, B, S = tr["workers"], tr["batch_per_worker"], tr["seq_len"]
    pool = token_pool(seed, arch.vocab_size, M, tr["pool_batches"], B, S)
    hooks = Hooks(pool, model, dims, dtype, seed)
    compiles = CompileCounter()
    kw = dict(workers=M, devices=devices, seq=S, batch_per_worker=B, lr=tr["lr"],
              algo=tr["algo"], gossip=tr["gossip"], seed=seed,
              monitor_every=tr["monitor_every"], log_every=tr["log_every"])
    trace_dir = tempfile.mkdtemp(prefix="chip-bench-trace-") if trace else None
    try:
        with stand_ins(hooks) as train, contextlib.redirect_stdout(log):
            train(arch, rounds=CAL_ROUNDS, **kw)
            t_cal = time.perf_counter()
            s = hooks.stamps
            round_s = (t_cal - s[1]) / (CAL_ROUNDS - 1)
            gc.collect()
            n = WINDOW_FIRST + max(1, round(seconds / round_s))
            traced = None
            if trace:
                first = WINDOW_FIRST + TRACE_SKIP
                traced = (first, first + tr["monitor_every"])
                n = max(n, traced[1] + 1)
            hooks.reset(probe=True, trace_rounds=traced, trace_dir=trace_dir)
            run = train(arch, rounds=n, **kw)
            t_end = time.perf_counter()
        s = hooks.stamps
        mem = memory_peak(devices)
        window_compiles = sum(1 for t in compiles.at if t > s[WINDOW_FIRST])
        losses = [r.loss for r in run.rounds]
        draws = [(r.neighbors, r.weights) for r in run.rounds[:CHECKED_ROUNDS]]
        prog = {"loss": losses[:CHECKED_ROUNDS], "mom1": hooks.mom1, "change": hooks.change}
        del run
        gc.collect()

        ends = [s[r] for r in range(WINDOW_FIRST + 1, n)] + [t_end]
        kind = devices[0].device_kind
        ctx = SimpleNamespace(
            rounds_s=[e - s[r] for r, e in zip(range(WINDOW_FIRST, n), ends)],
            window_s=t_end - s[WINDOW_FIRST],
            tokens_per_round=M * B * S,
            chips=len(devices),
            setup_s=s[WINDOW_FIRST] - t_start,
            round_flops=round_flops(model, dims, M, B, S),
            peaks=peaks(kind) if devices[0].platform == "tpu" else None,
            memory_peak_bytes=mem,
            trace=None,
            spans=None,
            model=model, dims=dims, workers=M, batch_per_worker=B, seq_len=S,
        )
        result_extra = {}
        if trace:
            rows = trace_mod.read_rows(trace_dir)
            ctx.trace = trace_mod.reduce(rows)
            ctx.spans = spans.reduce(rows, spans.op_scopes(hooks.text))
            del rows
            if ctx.trace is not None:
                t = ctx.trace
                br = result_extra["breakdown"] = trace_mod.breakdown(t, tr["monitor_every"])
                if ctx.spans is not None:
                    br["idle_by_span"] = ctx.spans["idle_by_span"][:10]
                result_extra["busy_s"] = sum(c["busy_ns"] for c in t["chips"]) / len(t["chips"]) * 1e-9
                result_extra["window_s"] = t["window_ns"] * 1e-9
        metrics = {}
        for m in cell.metrics:
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        print(f"window: {len(ctx.rounds_s)} rounds in {ctx.window_s:.3f}s "
              f"(calibrated round {round_s:.4f}s), compiles in window: {window_compiles}",
              file=log)

        t_ref = time.perf_counter()
        p0 = jax.jit(model.init, static_argnums=(0, 2))(dims, hooks.key, dtype)
        ref = reference.rounds(
            model, dims, p0, [pool[:, r % pool.shape[1]] for r in range(CHECKED_ROUNDS)],
            draws, lr=tr["lr"], momentum=tr["momentum"],
            weight_decay=tr["weight_decay"], devices=devices)
        del p0
        print(f"reference: {time.perf_counter() - t_ref:.1f}s", file=log)
        nums = check.numbers(prog, ref)
        correct = check.verdict(nums, cell.limits)
        failed = sum(1 for x in losses[WINDOW_FIRST:] if not np.isfinite(x))
        out = {
            "correct": bool(correct and failed == 0),
            "attempted": len(ctx.rounds_s),
            "failed": failed,
            "metrics": metrics,
            "device": {"platform": devices[0].platform, "kind": kind,
                       "count": len(devices), "memory_peak_bytes": mem},
        }
        if trace:
            out["device"]["busy_s"] = result_extra.get("busy_s")
            out["device"]["window_s"] = result_extra.get("window_s")
            if "breakdown" in result_extra:
                out["breakdown"] = result_extra["breakdown"]
        out["checks"] = {k: {"value": nums[k], "limit": cell.limits.get(k)}
                         for k in check.NUMBERS}
        for k in check.NUMBERS:
            print(f"check {k} = {nums[k]!r} (limit {cell.limits.get(k)!r})", file=log)
        return out
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
