"""The chip benchmark's yardstick on the CPU: the trace reduction on a
hand-built trace, the peak table, and the model-FLOP count."""

import pytest

from _paths import BENCH

import flops  # noqa: E402
import harness  # noqa: E402
import xplane  # noqa: E402
from peaks import peaks  # noqa: E402
from types import SimpleNamespace  # noqa: E402

QWEN2 = harness.model_of({"model_type": "qwen2"})

OPS, MODS = xplane.OPS_LINE, xplane.MODULES_LINE


def _fixture():
    """Two chips, rounds 10..12 marked at 0, 100, 200 us on the host.

    chip 0: program runs [5, 85] and [105, 185]; inside, compute
    [5, 40] and [60, 85], an all-gather [30, 70] (exposed 40..60),
    likewise shifted by 100 in round 11.
    chip 1: program [10, 90] and [110, 190] with all-reduce [20, 30]
    hidden under compute [10, 90].
    """
    us = 1000.0
    rows = [("/host:CPU", "python", f"{xplane.MARK}.{r}", t * us, 0.0)
            for r, t in ((10, 0), (11, 100), (12, 200))]
    for k in (0, 100):
        rows += [
            ("/device:TPU:0", MODS, "jit_train_step(7)", (5 + k) * us, 80 * us),
            ("/device:TPU:0", OPS, "fusion.1", (5 + k) * us, 35 * us),
            ("/device:TPU:0", OPS, "all-gather-start.2", (30 + k) * us, 40 * us),
            ("/device:TPU:0", OPS, "fusion.3", (60 + k) * us, 25 * us),
            ("/device:TPU:1", MODS, "jit_train_step(7)", (10 + k) * us, 80 * us),
            ("/device:TPU:1", OPS, "convolution.4", (10 + k) * us, 80 * us),
            ("/device:TPU:1", OPS, "all-reduce.5", (20 + k) * us, 10 * us),
        ]
    rows.append(("/device:TPU:0", MODS, "jit_small(3)", 190 * us, 5 * us))
    rows.append(("/device:TPU:0", OPS, "copy.9", 190 * us, 5 * us))
    rows.append(("/device:TPU:0", OPS, "fusion.1", 300 * us, 50 * us))  # after the window
    return rows


def test_reduction_of_a_hand_built_trace():
    red = xplane.reduce(_fixture())
    assert red["window_ns"] == 200e3 and red["rounds"] == 2
    c0, c1 = red["chips"]
    assert c0["busy_ns"] == 2 * 80e3 + 5e3
    assert c1["busy_ns"] == 2 * 80e3
    assert c0["program"] == "jit_train_step" and c0["program_runs"] == 2
    assert c0["program_ns"] == 160e3
    assert c0["collective_ns"] == 80e3 and c0["collective_exposed_ns"] == 40e3
    assert c1["collective_ns"] == 20e3 and c1["collective_exposed_ns"] == 0.0
    assert c0["gaps"] == [(0.0, 5e3), (85e3, 105e3), (185e3, 190e3), (195e3, 200e3)]
    assert red["ops"]["fusion.1"] == pytest.approx(70e3 * 1e-9 / 2)


def test_metric_readers_on_the_fixture():
    red = xplane.reduce(_fixture())
    ctx = SimpleNamespace(trace=red, chips=2, round_flops=2 * 197e12 * 80e-6,
                          peaks=peaks("TPU v5 lite"), memory_peak_bytes=3 * 2**30,
                          rounds_s=[0.1, 0.2, 0.3], window_s=0.6, tokens_per_round=1000,
                          setup_s=12.5)
    read = lambda n: harness._reader(n)(ctx)  # noqa: E731
    assert read("step_mfu") == pytest.approx(100.0)
    assert read("idle_share") == pytest.approx(100 * (1 - 160e3 / 200e3))
    assert read("peak_hbm_gib") == 3.0
    assert read("tokens_per_s") == pytest.approx(3 * 1000 / 0.6 / 2)
    assert read("round_ms_p95") == pytest.approx(290.0)
    assert read("setup_s") == 12.5
    ctx.trace = None
    assert read("step_mfu") is None and read("idle_share") is None


def test_op_names_of_a_tpu_trace():
    name = "%all-gather-start.3 = (f32[2,16]{1,0}, f32[8,16]{1,0}) all-gather-start(...)"
    assert xplane.op_name(name) == "all-gather-start.3"
    assert xplane.opcode(name) == "all-gather-start" and xplane.is_collective(name)
    assert not xplane.is_collective("%fusion.12 = f32[2]{0} fusion(...)")


def test_gaps_are_labelled_by_the_loop():
    red = xplane.reduce(_fixture())
    b = xplane.breakdown(red, monitor_every=10)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    label, secs = b["idle_gaps"][0]
    assert secs == pytest.approx(20e-6)
    assert "start of round 11" in label and "loss read-back" in label
    assert "NetworkMonitor.step" in xplane.label_gap(190e3, 210e3, {20: 200e3}, 10)
    assert xplane.label_gap(1, 2, {20: 200e3}, 10) == "launcher loop, unattributed"


def test_no_window_reads_nothing():
    assert xplane.reduce([r for r in _fixture() if not r[2].startswith(xplane.MARK)]) is None


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_model_flops_against_a_hand_count():
    d = QWEN2.Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10,
             rope_theta=1e4, rms_eps=1e-6, init_std=0.02, qkv_bias=True)
    S = 3
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 64+32+32+64+384 = 576
    matmuls = 2 * S * (2 * 576 + 8 * 10)
    # causal pairs 3+2+1 = 6, q width 8: scores and values, 2 FLOPs each
    attention = 2 * 2 * 2 * 6 * 8
    assert QWEN2.forward_flops_per_sequence(d, S) == matmuls + attention
    assert flops.round_flops(QWEN2, d, 2, 3, S) == 3 * 2 * 3 * (matmuls + attention)


def test_model_flops_of_the_configurations():
    import json

    conf = json.loads((BENCH / "configs" / "qwen1.5-0.5b.json").read_text())
    d = QWEN2.Dims.from_config(dict(conf, num_hidden_layers=24))  # the published depth
    # 463,987,712 parameters a replica, less the norms and biases, and at
    # seq 1 one query-key pair a layer
    weights_ = 463_987_712 - 24 * (2 * 1024 + 3 * 1024) - 1024
    assert QWEN2.forward_flops_per_sequence(d, 1) == 2 * weights_ + 24 * 4 * 1024
