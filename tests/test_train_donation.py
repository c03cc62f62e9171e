"""The launcher's round donates its params and optimizer state, on the CPU at
the reduced size: the inputs are deleted, and training gives, bit for bit,
what the same round gives undonated."""

import jax
import numpy as np

import repro.launch.train as loop
from repro.configs.base import get_arch
from repro.launch.train import make_step, train
from repro.train.trainer import init_stacked

CFG = get_arch("qwen1.5-0.5b").reduced()
M, B, S = 2, 2, 32
KW = dict(workers=M, seq=S, batch_per_worker=B, monitor_every=2, log_every=100)


def _inputs(opt):
    params, opt_state = jax.jit(lambda k: init_stacked(CFG, opt, M, k))(
        jax.random.PRNGKey(0))
    batch = {k: np.zeros((M, B, S), np.int32) for k in ("tokens", "labels")}
    gi = {"neighbors": np.array([1, 0], np.int32),
          "weights": np.full(M, 0.5, np.float32), "lr": np.float32(0.02)}
    return params, opt_state, batch, gi


def test_round_deletes_the_state_it_was_given():
    opt, step = make_step(CFG, M)
    params, opt_state, batch, gi = _inputs(opt)
    state = jax.tree_util.tree_leaves((params, opt_state))
    assert len({x.unsafe_buffer_pointer() for x in state}) == len(state)
    new = step(params, opt_state, batch, gi)
    assert all(x.is_deleted() for x in state)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(new))


def _undonated(make):
    def make_undonated(*args, **kw):
        opt, fn = make(*args, **kw)
        return opt, jax.jit(fn.__wrapped__)
    return make_undonated


def test_undonated_round_keeps_its_inputs():
    opt, step = _undonated(make_step)(CFG, M)
    params, opt_state, batch, gi = _inputs(opt)
    step(params, opt_state, batch, gi)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves((params, opt_state)))


def test_train_matches_the_undonated_round(monkeypatch):
    donated = train(CFG, rounds=5, **KW)
    monkeypatch.setattr(loop, "make_step", _undonated(loop.make_step))
    kept = train(CFG, rounds=5, **KW)
    assert [r.loss for r in donated.rounds] == [r.loss for r in kept.rounds]
    for a, b in zip(jax.tree_util.tree_leaves((donated.params, donated.opt_state)),
                    jax.tree_util.tree_leaves((kept.params, kept.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
