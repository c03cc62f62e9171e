"""The launcher loop's spans and counters, and the round program's named
scopes, on the CPU at the reduced size."""

import gc
import glob

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.launch.train import NESTED, _Watch, make_step, train

CFG = get_arch("qwen1.5-0.5b").reduced()
PER_ROUND = {"stage", "draw", "dispatch", "device_wait", "readback", "ema", "log"}
KW = dict(workers=2, seq=32, batch_per_worker=2, log_every=4)


@pytest.fixture(scope="module")
def run():
    hooks = list(gc.callbacks)
    out = train(CFG, rounds=12, monitor_every=5, **KW)
    assert gc.callbacks == hooks  # the collector's hook is gone again
    return out


def test_every_round_records_its_phases(run):
    assert [r.round for r in run.rounds] == list(range(1, 13))
    for r in run.rounds:
        want = PER_ROUND | ({"monitor"} if r.round in (5, 10) else set())
        assert set(r.spans) - set(NESTED) == want, r.round
        assert all(v >= 0.0 for v in r.spans.values())


def test_refreshes_compiles_and_setup(run):
    assert [f.round for f in run.refreshes] == [5, 10]
    for f, r in zip(run.refreshes, (run.rounds[4], run.rounds[9])):
        assert f.ms == pytest.approx(r.spans["monitor"] * 1e3)
        assert f.n_solves >= 1 and f.n_warm_used <= f.n_solves and f.n_pivots >= 0
        assert isinstance(f.applied, bool)
    # the initialiser and the round program compile in set-up; no round does
    assert run.compiles >= 2 and run.setup["backend_compile"] > 0
    assert not any("backend_compile" in r.spans for r in run.rounds)
    assert {"init", "compile"} <= set(run.setup) <= {"init", "compile", *NESTED}
    assert run.setup["compile"] > 0
    for gen, (n, secs) in run.gc.items():
        assert gen in (0, 1, 2) and n >= 1 and secs >= 0.0
    # every collection's seconds went to the set-up or round it fell in
    recs = [run.setup] + [r.spans for r in run.rounds]
    assert sum(s for _, s in run.gc.values()) == pytest.approx(
        sum(rec.get("gc", 0.0) for rec in recs))


def test_watch_counts_collections_and_compiles():
    rec = {}
    with _Watch(rec) as w:
        gc.collect()
        jax.jit(lambda x: x + 1)(np.float32(1))  # a new function: one compile
    assert w.gc[2][0] == 1 and rec["gc"] == pytest.approx(w.gc[2][1])
    assert w.compiles == 1 and rec["backend_compile"] > 0
    gc.collect()
    jax.jit(lambda x: x * 3)(np.float32(1))
    assert w.gc[2][0] == 1 and w.compiles == 1  # nothing is counted after exit


def test_checkpoint_span_only_when_it_saves(tmp_path, capsys):
    out = train(CFG, rounds=4, monitor_every=100, ckpt_dir=str(tmp_path), ckpt_every=2, **KW)
    assert ["checkpoint" in r.spans for r in out.rounds] == [False, True, False, True]
    # the run ends with its slowest rounds, each with all of its spans
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("slowest rounds: ")]
    rounds = line.removeprefix("slowest rounds: ").split("; ")
    assert sorted(int(r.split()[0]) for r in rounds) == [1, 2, 3, 4]
    assert all("device_wait=" in r and "stage=" in r for r in rounds)
    assert sum("checkpoint=" in r for r in rounds) == 2


def _events(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                yield plane.name, line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def test_profile_holds_rounds_with_their_phases_nested(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        train(CFG, rounds=3, monitor_every=100, **KW)
    finally:
        jax.profiler.stop_trace()
    evs = list(_events(tmp_path))
    rounds = [e for e in evs if e[2] == "round"]
    assert len(rounds) == 3
    assert len({(p, ln) for p, ln, *_ in rounds}) == 1  # one host line
    line = rounds[0][:2]
    for _, _, _, s, e in rounds:
        inside = {n for p, ln, n, a, b in evs
                  if (p, ln) == line and n in PER_ROUND and s <= a and b <= e}
        assert inside == PER_ROUND
    assert sum(e[2] == "compile" for e in evs) == 1


def test_round_program_carries_the_scopes():
    opt, step = make_step(CFG, 2)
    M, B, S = 2, 2, 16
    from repro.train.trainer import abstract_stacked

    params, opt_state = abstract_stacked(CFG, opt, M)
    batch = {k: jax.ShapeDtypeStruct((M, B, S), "int32") for k in ("tokens", "labels")}
    gi = {"neighbors": jax.ShapeDtypeStruct((M,), "int32"),
          "weights": jax.ShapeDtypeStruct((M,), "float32"),
          "lr": jax.ShapeDtypeStruct((), "float32")}
    text = step.lower(params, opt_state, batch, gi).compile().as_text()
    for scope in ("forward_backward", "optimizer", "gossip_pull", "gossip_mix"):
        assert f"/{scope}/" in text, scope
