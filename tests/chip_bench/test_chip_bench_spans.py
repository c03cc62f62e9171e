"""The reduction of the program's spans and scopes (``spans.py``) and its
metric readers, on a hand-built trace."""

from types import SimpleNamespace

import pytest

from _paths import BENCH  # noqa: F401  (puts the benchmark on sys.path)

import spans  # noqa: E402
import xplane  # noqa: E402

US = 1000.0
HOST = ("/host:CPU", "python")
OPS = xplane.OPS_LINE
FB = {"forward_backward": 1.0}
SCOPES = {"while.1": FB, "fusion.2": FB, "fusion.3": FB, "fusion.4": {"optimizer": 1.0},
          "fusion.5": {"gossip_mix": 0.6, "optimizer": 0.4}}  # copy.6 has no scope


def _fixture():
    """Rounds 10..12 start at 0, 100, 200 us; their marks, at +2 us, bound
    the window [2, 202].

    Host, each round: ``round`` [0, 95], ``stage`` [0, 20], ``device_wait``
    [20, 85], then ``monitor`` [85, 95] in round 10 and ``ema`` [185, 195]
    in round 11; a ``gc`` span [105, 108] inside round 11's stage; nothing
    in [95, 100].
    Device, each round: ``while.1`` [20, 70] holding ``fusion.2`` [25, 45]
    and ``fusion.3`` [50, 60]; then ``fusion.4`` [70, 80], ``fusion.5``
    [80, 85], ``copy.6`` [85, 90].
    """
    rows = []
    for r, o in ((10, 0), (11, 100), (12, 200)):
        rows += [(*HOST, f"{xplane.MARK}.{r}", (o + 2) * US, 0.0),
                 (*HOST, "round", o * US, 95 * US),
                 (*HOST, "stage", o * US, 20 * US),
                 (*HOST, "device_wait", (o + 20) * US, 65 * US),
                 (*HOST, "$builtins isinstance", (o + 30) * US, 1 * US)]
        for name, s, d in (("while.1", 20, 50), ("fusion.2", 25, 20), ("fusion.3", 50, 10),
                           ("fusion.4", 70, 10), ("fusion.5", 80, 5), ("copy.6", 85, 5)):
            rows.append(("/device:TPU:0", OPS, f"%{name} = f32[8]{{0}} op(...)",
                         (o + s) * US, d * US))
    rows += [(*HOST, "monitor", 85 * US, 10 * US),
             (*HOST, "ema", 185 * US, 10 * US),
             (*HOST, "gc", 105 * US, 3 * US)]
    return rows


def test_self_time_counts_nested_ops_once():
    red = spans.reduce(_fixture(), SCOPES)
    per_round = {k: v / red["rounds"] * 1e9 / US for k, v in red["scopes"].items()}
    assert red["rounds"] == 2 and red["window_ns"] == 200 * US
    # fusion.5's 5 us a round are shared 3 to the mix, 2 to the optimizer
    assert per_round == pytest.approx({"forward_backward": 50, "optimizer": 12,
                                       "gossip_mix": 3, "unscoped": 5})
    # the same window by op, as xplane sums it, counts the while's body twice
    busy = xplane.reduce(_fixture())
    assert sum(red["scopes"].values()) == pytest.approx(busy["chips"][0]["busy_ns"] * 1e-9)
    assert sum(busy["ops"].values()) > sum(red["scopes"].values())


def test_idle_time_goes_to_the_innermost_span():
    red = spans.reduce(_fixture(), SCOPES)
    idle = {k: v * 1e9 / US for k, v in red["idle_by_span"]}
    # [2, 20] stage; [90, 120] monitor 5, outside 5, stage 17, gc 3;
    # [190, 202] ema 5, outside 5, stage 2
    assert idle == pytest.approx({"stage": 37, "monitor": 5, "ema": 5, spans.OUTSIDE: 10,
                                  "gc": 3})
    assert [k for k, _ in red["idle_by_span"]][0] == "stage"


def test_host_spans_are_clipped_to_the_window():
    host = {k: (n, s * 1e9 / US) for k, (n, s) in spans.reduce(_fixture(), SCOPES)["host"].items()}
    assert host["stage"] == (3, pytest.approx(40))
    assert host["round"] == (3, pytest.approx(93 + 95 + 2))
    assert host["device_wait"] == (2, pytest.approx(130))
    assert host["monitor"] == (1, pytest.approx(10))
    assert "$builtins isinstance" not in host


def test_no_window_or_no_device_reads_nothing():
    rows = _fixture()
    assert spans.reduce([r for r in rows if not r[2].startswith(xplane.MARK)], SCOPES) is None
    assert spans.reduce([r for r in rows if r[1] != OPS], SCOPES) is None


def test_innermost_prefers_the_latest_start():
    pieces = spans.innermost([(0, 10, "a"), (2, 4, "b"), (2, 3, "c"), (8, 12, "d")], 1, 11)
    assert pieces == [(1, 2, "a"), (2, 3, "c"), (3, 4, "b"), (4, 8, "a"), (8, 11, "d")]


def test_scopes_from_hlo_text():
    text = (
        '%fc (param_0.1: f32[2,8], param_1.2: f32[2,8], param_2.3: f32[2,8]) '
        '-> (f32[2,8], f32[2,8]) {\n'
        '  %param_0.1 = f32[2,8]{1,0} parameter(0)\n'
        '  %param_1.2 = f32[2,8]{1,0} parameter(1)\n'
        '  %mul.1 = f32[2,8]{1,0} multiply(%param_0.1, %param_1.2), '
        'metadata={op_name="jit(train_step)/optimizer/mul" stack_frame_id=2}\n'
        '  %param_2.3 = f32[2,8]{1,0} parameter(2)\n'
        '  %constant.9 = f32[]{:T(128)} constant(0)\n'
        '  %pad.2 = f32[2,8]{1,0} pad(%param_2.3, %constant.9), padding=0_0x0_0\n'
        '  %select.3 = f32[2,8]{1,0} select(%pad.2, %pad.2, %pad.2), '
        'metadata={op_name="jit(train_step)/gossip_pull/jit(_take)/select_n"}\n'
        '  %add.4 = f32[2,8]{1,0} add(%mul.1, %select.3), '
        'metadata={op_name="jit(train_step)/gossip_mix/jit(mix_stacked_tree)/add"}\n'
        '  ROOT %tuple.5 = (f32[2,8]{1,0}, f32[2,8]{1,0}) tuple(%add.4, %mul.1)\n'
        '}\n\n'
        'ENTRY %main.1 (p: f32[8]) -> f32[8] {\n'
        '  %fusion.6 = (f32[2,8]{1,0:T(8,128)}, f32[2,8]{1,0}) fusion(%a, %b, /*index=2*/%c), '
        'kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(train_step)/gossip_mix/jit(mix_stacked_tree)/add"}\n'
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%absent, '
        'metadata={op_name="jit(train_step)/forward_backward/while/body/add" stack_frame_id=3}\n'
        '  %fusion.8.remat = f32[8]{0} fusion(%q), '
        'metadata={op_name="jit(train_step)/transpose(jvp(forward_backward))/dot_general"}\n'
        '  %gather.9 = f32[2,8]{1,0} gather(%a, %b), '
        'metadata={op_name="jit(train_step)/gossip_pull/jit(_take)/gather"}\n'
        '  %copy.10 = f32[8]{0} copy(%c), metadata={op_name="jit(train_step)/optimizers/x"}\n'
        '  ROOT %p = f32[8]{0} parameter(0)\n'
        '}\n'
    )
    got = spans.op_scopes(text)
    # the fusion's 5 arrays of 64 bytes: both parameters of the optimizer's
    # multiply and the momentum it writes go to the optimizer; the padded
    # parameter to the pull that selects from it; the new params to the mix
    assert got["fusion.6"] == pytest.approx({"optimizer": 0.6, "gossip_pull": 0.2,
                                             "gossip_mix": 0.2})
    assert {k: got[k] for k in ("fusion.7", "fusion.8.remat", "gather.9", "copy.10")} == {
        "fusion.7": FB, "fusion.8.remat": FB, "gather.9": {"gossip_pull": 1.0},
        "copy.10": {"unscoped": 1.0}}
    assert got["pad.2"] == {"unscoped": 1.0} and got["mul.1"] == {"optimizer": 1.0}


def _ctx(with_spans=True):
    red = spans.reduce(_fixture(), SCOPES)
    ctx = SimpleNamespace(trace=xplane.reduce(_fixture()))
    if with_spans:
        ctx.spans = red
    return ctx


@pytest.mark.parametrize("name,want", [
    ("loop_host_ms", (40 + 10) * US * 1e-6 / 2),  # stage and ema spans, 2 rounds
    ("monitor_refresh_ms", 10 * US * 1e-6),
    ("fwd_bwd_ms", 50 * US * 1e-6),
    ("optimizer_ms", 12 * US * 1e-6),
    ("gossip_ms", 3 * US * 1e-6),
])
def test_span_readers(name, want):
    import harness

    read = harness._reader(name)
    assert read(_ctx()) == pytest.approx(want)
    assert read(_ctx(with_spans=False)) is None  # a run that keeps no spans
    ctx = _ctx()
    ctx.trace = None
    assert read(ctx) is None


def test_a_scope_fused_away_reads_zero():
    import harness

    ctx = _ctx()
    del ctx.spans["scopes"]["optimizer"]
    assert harness._reader("optimizer_ms")(ctx) == 0.0
    assert harness._reader("fwd_bwd_ms")(ctx) == pytest.approx(50 * US * 1e-6)


@pytest.mark.parametrize("name", ["loop_host_ms", "monitor_refresh_ms", "fwd_bwd_ms",
                                  "optimizer_ms", "gossip_ms"])
def test_span_readers_on_a_program_without_spans(name):
    import harness

    rows = [r for r in _fixture() if r[2] not in spans.SPANS]
    ctx = SimpleNamespace(trace=xplane.reduce(rows), spans=spans.reduce(rows, {}))
    assert harness._reader(name)(ctx) is None


def test_a_traced_run_hands_the_readers_its_spans(monkeypatch):
    """A whole traced run on the CPU, whose trace holds no device: the
    harness reads the trace's rows (here the fixture's) and reduces them
    with the scopes of the compiled round's own text."""
    import time

    import jax

    import harness
    from _tiny import BENCHMARK, SEED, tiny_cell

    texts = []

    def op_scopes(text):
        texts.append(text)
        return SCOPES

    monkeypatch.setattr(harness.trace_mod, "read_rows", lambda trace_dir: _fixture())
    monkeypatch.setattr(harness.spans, "op_scopes", op_scopes)
    name = BENCHMARK["workloads"][0]["name"]
    cell = tiny_cell(name)
    cell.metrics = harness.resolve(name, True, BENCHMARK).metrics
    out = harness.run_cell(cell, seed=SEED, seconds=0.3, trace=True,
                           devices=jax.devices()[:1], t_start=time.perf_counter())
    assert out["correct"] is True
    assert len(texts) == 1 and "forward_backward" in texts[0] and "gossip_pull" in texts[0]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["fwd_bwd_ms"] == pytest.approx(50 * US * 1e-6)
    assert got["optimizer_ms"] == pytest.approx(12 * US * 1e-6)
    assert got["gossip_ms"] == pytest.approx(3 * US * 1e-6)
    assert got["loop_host_ms"] == pytest.approx((40 + 10) * US * 1e-6 / 2)
    assert got["monitor_refresh_ms"] == pytest.approx(10 * US * 1e-6)
    idle = dict(out["breakdown"]["idle_by_span"])
    assert idle["stage"] == pytest.approx(37 * US * 1e-9)
    assert out["device"]["window_s"] == pytest.approx(200 * US * 1e-9)
