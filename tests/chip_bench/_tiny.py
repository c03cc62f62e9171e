"""Tiny cells for driving whole runs of the chip benchmark on the CPU."""

import json
import time

import jax

from _paths import ROOT

import harness  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 4099


def tiny_cell(name: str, kv_heads: int = 4) -> harness.Cell:
    """The cell ``name`` with its traffic, limits and metrics, on a
    float32 model of the same family at a small size (GQA when
    ``kv_heads`` < 4)."""
    real = harness.resolve(name, False, BENCHMARK)
    conf = {"model_type": real.config["model_type"], "hidden_size": 64,
            "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": kv_heads, "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
            "hidden_act": "silu", "initializer_range": 0.02,
            "tie_word_embeddings": True, "qkv_bias": True,
            "program": {"registered": real.config["program"]["registered"], "set": dict(
                real.config["program"]["set"], n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=kv_heads, d_ff=128, vocab_size=256, head_dim=16,
                dtype="float32", remat=False)}}
    traffic = dict(real.traffic, seq_len=32)
    return harness.Cell(name=name, chips=1, config=conf, traffic=traffic,
                        limits=real.limits, metrics=real.metrics)


def run(cell: harness.Cell, seed: int = SEED) -> dict:
    return harness.run_cell(cell, seed=seed, seconds=0.3, trace=False,
                            devices=jax.devices()[:1], t_start=time.perf_counter())
