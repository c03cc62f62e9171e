"""Models found by a configuration's ``model_type``: the Qwen2 module keeps
the weights and FLOPs the benchmark had, a file that names no module is
refused, and a model that the benchmark does not ship (``toy_moe.py``: an
untied head, routed experts) runs a whole cell to ``correct`` when it comes
as files alone."""

import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _paths import BENCH, ROOT
from _tiny import BENCHMARK, SEED

import check  # noqa: E402
import harness  # noqa: E402
from flops import round_flops  # noqa: E402

QWEN2 = harness.model_of({"model_type": "qwen2"})
CELL = BENCHMARK["workloads"][0]["name"]


def _tiny_conf():
    return {"model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
            "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-6, "hidden_act": "silu",
            "initializer_range": 0.02, "tie_word_embeddings": True, "qkv_bias": True,
            "program": {"registered": "internvl2-1b", "set": dict(
                name="tiny", family="dense", n_vis_tokens=0, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16,
                qkv_bias=True)}}


def test_benchmark_weights_have_the_programs_layout():
    from repro.models import lm

    conf = _tiny_conf()
    cfg = harness.program_config(conf)
    model = harness.model_of(conf)
    dims = model.Dims.from_config(conf)
    ours = jax.eval_shape(lambda: model.init(dims, jax.random.PRNGKey(0), jnp.bfloat16))
    theirs = lm.abstract_params(cfg)
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_program_config_refuses_a_file_that_differs():
    conf = _tiny_conf()
    conf["intermediate_size"] = 256
    with pytest.raises(ValueError):
        harness.program_config(conf)


def _frozen_init(d, key, dtype):
    """The weights as the benchmark drew them before models had modules of
    their own: one ``fold_in(key, i)`` for the i-th leaf in this order."""
    L, D, F = d.layers, d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    order = [("blocks.attn.bk", (L, kv)), ("blocks.attn.bq", (L, q)),
             ("blocks.attn.bv", (L, kv)), ("blocks.attn.wk", (L, D, kv)),
             ("blocks.attn.wo", (L, q, D)), ("blocks.attn.wq", (L, D, q)),
             ("blocks.attn.wv", (L, D, kv)), ("blocks.ln1.scale", (L, D)),
             ("blocks.ln2.scale", (L, D)), ("blocks.mlp.w_down", (L, F, D)),
             ("blocks.mlp.w_gate", (L, D, F)), ("blocks.mlp.w_up", (L, D, F)),
             ("embed.table", (d.vocab, D)), ("final_norm.scale", (D,))]
    out = {}
    for i, (name, shape) in enumerate(order):
        leaf = name.rsplit(".", 1)[1]
        if leaf == "scale":
            out[name] = jnp.ones(shape, dtype)
        elif leaf.startswith("b"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out[name] = (draw * d.init_std).astype(dtype)
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qwen2_weights_are_the_benchmarks_as_before(dtype):
    d = QWEN2.Dims.from_config(_tiny_conf())
    key = jax.random.PRNGKey(SEED)
    got = jax.tree_util.tree_flatten_with_path(jax.jit(
        QWEN2.init, static_argnums=(0, 2))(d, key, dtype))[0]
    want = jax.jit(_frozen_init, static_argnums=(0, 2))(d, key, dtype)
    assert [jax.tree_util.keystr(p, simple=True, separator=".") for p, _ in got] == list(want)
    for (_, a), b in zip(got, want.values()):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_round_flops_of_the_short_cell_are_as_before():
    c = harness.resolve("qwen15-m2-short512", False, BENCHMARK)
    tr = c.traffic
    d = QWEN2.Dims.from_config(c.config)
    assert round_flops(QWEN2, d, tr["workers"], tr["batch_per_worker"],
                       tr["seq_len"]) == 4_540_518_629_376


TOY_CONF = {
    "model_type": "toy_moe", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 256, "num_local_experts": 4, "num_experts_per_tok": 2,
    "capacity_factor": 2.0, "router_aux_loss_coef": 0.01, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "program": {"registered": "phi3.5-moe-42b-a6.6b", "set": {
        "name": "toy-moe", "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "dtype": "float32", "remat": False,
        "microbatches": 2, "moe": {"n_experts": 4, "top_k": 2, "capacity_factor": 2.0}}},
}


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark's files with one more configuration, its
    model module, traffic and limits, and no other change; the harness
    reads that copy."""
    root = tmp_path / "checkout"
    here = root / BENCH.relative_to(ROOT)
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(Path(__file__).with_name("toy_moe.py"), here / "models" / "toy_moe.py")
    (here / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONF))
    real = harness.resolve(CELL, False, BENCHMARK)
    (here / "traffic" / "m2-tiny32.json").write_text(json.dumps(dict(real.traffic, seq_len=32)))
    (here / "limits" / "toy-moe-m2.json").write_text(json.dumps(real.limits))
    bench = dict(BENCHMARK)
    bench["configs"] = BENCHMARK["configs"] + [
        {"name": "toy-moe", "file": str((here / "configs" / "toy-moe.json").relative_to(root))}]
    bench["workloads"] = BENCHMARK["workloads"] + [
        {"name": "toy-moe-m2", "config": "toy-moe", "traffic": "m2-tiny32", "chips": 1}]
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "ROOT", root)
    return bench


def test_a_model_added_as_files_runs_to_correct(bench_copy):
    cell = harness.resolve("toy-moe-m2", False, bench_copy)
    cfg = harness.program_config(cell.config)
    assert (cfg.family, cfg.tie_embeddings, cfg.moe.n_experts) == ("moe", False, 4)
    out = harness.run_cell(cell, seed=SEED, seconds=0.3, trace=False,
                           devices=jax.devices()[:1], t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    for k in check.NUMBERS:  # float32 program against the float32 reference
        assert out["checks"][k]["value"] < 1e-5, out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.metrics}


def test_an_unknown_model_type_is_refused_by_name(bench_copy):
    conf = dict(TOY_CONF, model_type="no_such_model")
    path = harness.HERE / "configs" / "toy-moe.json"
    path.write_text(json.dumps(conf))
    with pytest.raises(FileNotFoundError, match="models/no_such_model.py"):
        harness.resolve("toy-moe-m2", False, bench_copy)
