"""A whole benchmark run, the chip's look skipped, with the timed path
broken underneath: ``correct`` comes out false for each fault a training
cell can have (a step that returns its state unchanged, half of each batch
left out with the mean over the rest, the exchange between replicas left
out)."""

import jax
import pytest

from _tiny import BENCHMARK, run, tiny_cell

import repro.launch.train as loop  # noqa: E402


def _unchanged(fn):
    return lambda p, o, b, g: (p, o, fn(p, o, b, g)[2])


def _half_batch(fn):
    return lambda p, o, b, g: fn(
        p, o, jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], b), g)


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch}


@pytest.mark.parametrize("fault", [*FAULTS, "no_pull"])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    real = loop.make_step

    def broken(cfg, M, *, algo="netmax", gossip="gather", mesh=None):
        if fault == "no_pull":
            return real(cfg, M, algo="local", gossip=gossip, mesh=mesh)
        opt, fn = real(cfg, M, algo=algo, gossip=gossip, mesh=mesh)
        return opt, jax.jit(FAULTS[fault](fn))

    monkeypatch.setattr(loop, "make_step", broken)
    out = run(tiny_cell(BENCHMARK["workloads"][0]["name"]))
    assert out["correct"] is False, out["checks"]
