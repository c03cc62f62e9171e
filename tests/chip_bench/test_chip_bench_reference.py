"""The plain float32 reference against the program at a small size, for the
layout of each configuration (MHA and GQA), through a whole benchmark run;
and the control (the reference in int8 in the program's place) fails the
limits."""

import numpy as np
import pytest

from _tiny import BENCHMARK, SEED, run, tiny_cell

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
from tokens import token_pool  # noqa: E402

CELL = BENCHMARK["workloads"][0]["name"]


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_program_matches_the_reference(kv_heads):
    out = run(tiny_cell(CELL, kv_heads))
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    for k in check.NUMBERS:  # float32 program against the float32 reference
        assert out["checks"][k]["value"] < 1e-5, out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in tiny_cell(CELL).metrics}
    assert list(out)[-1] == "checks"


def test_control_fails_the_limits():
    import jax

    cell = tiny_cell(CELL)
    tr = cell.traffic
    model = harness.model_of(cell.config)
    dims = model.Dims.from_config(cell.config)
    M, B, S = tr["workers"], tr["batch_per_worker"], tr["seq_len"]
    pool = token_pool(SEED, dims.vocab, M, 3, B, S)
    draws = [(np.array([1, 0]), np.full(2, 0.25, np.float32))] * 3
    p0 = model.init(dims, jax.random.PRNGKey(SEED), np.float32)
    kw = dict(lr=tr["lr"], momentum=tr["momentum"], weight_decay=tr["weight_decay"],
              devices=jax.devices()[:1])
    batches = [pool[:, r] for r in range(3)]
    ref = reference.rounds(model, dims, p0, batches, draws, **kw)
    stated = harness.resolve(CELL, False, BENCHMARK).config["torch_dtype"]
    control = reference.rounds(model, dims, p0, batches, draws,
                               quant=reference.CONTROL[stated], **kw)
    nums = check.numbers(control, ref)
    assert not check.verdict(nums, cell.limits), nums
    assert check.verdict(check.numbers(ref, ref), cell.limits)
    assert harness.CHECKED_ROUNDS == 3
