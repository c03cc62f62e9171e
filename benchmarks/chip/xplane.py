"""Reduction of a profiler trace to device intervals and per-chip sums.

A trace is read into plain rows ``(plane, line, name, start_ns, dur_ns)``
(``read_rows``); everything else works on those rows, so tests can hand it
a small recorded or hand-built fixture.  Device planes are those whose name
starts with ``/device:``; on each, the ``XLA Ops`` line gives the intervals
in which an operation ran and the ``XLA Modules`` line the executions of
whole programs.  The window is the span between the first and last host
mark named ``MARK.<round>`` (the harness writes one at the start of every
traced round).
"""

from __future__ import annotations

import glob
import re

MARK = "bench.round"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def read_rows(trace_dir: str) -> list[tuple]:
    """Rows of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
    rows = []
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def marks(rows) -> dict[int, float]:
    """Round index -> host time (ns) of its start mark."""
    out = {}
    for _, _, name, start, _ in rows:
        if name.startswith(MARK + "."):
            out[int(name.rsplit(".", 1)[1])] = start
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` (a TPU trace's op event) -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """HLO opcode of an op, such as ``all-gather-start`` for ``all-gather-start.3``."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


def module_name(name: str) -> str:
    """``jit_train_step(123)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(rows) -> dict | None:
    """Per-chip sums over the marked window; None when there is no window
    or no device operation in it.

    Keys: ``window_ns``; ``rounds`` (marked rounds in the window); per chip,
    in device-plane order, ``busy_ns`` (union of op intervals),
    ``program``/``program_ns``/``program_runs`` (the module with the most
    device time, its total time and executions), ``collective_ns`` (union
    of collective op intervals) and ``collective_exposed_ns`` (the part of
    it when no other op runs); ``ops`` (op name -> device seconds, mean
    over chips) and ``gaps`` of chip 0 (idle intervals, ns).
    """
    m = marks(rows)
    if len(m) < 2:
        return None
    t0, t1 = min(m.values()), max(m.values())
    planes = sorted({p for p, *_ in rows if p.startswith("/device:")})
    chips = []
    ops_total: dict[str, float] = {}
    for plane in planes:
        ops = [(op_name(n), s, s + d) for p, ln, n, s, d in rows
               if p == plane and ln == OPS_LINE]
        mods = [(module_name(n), s, s + d) for p, ln, n, s, d in rows
                if p == plane and ln == MODULES_LINE]
        busy = clip(merge([(s, e) for _, s, e in ops]), t0, t1)
        if not busy:
            continue
        coll = clip(merge([(s, e) for n, s, e in ops if is_collective(n)]), t0, t1)
        other = merge([(s, e) for n, s, e in ops if not is_collective(n)])
        exposed = length(coll) - sum(length(clip(other, s, e)) for s, e in coll)
        per_mod: dict[str, list] = {}
        for n, s, e in mods:
            if s >= t0 and e <= t1:
                per_mod.setdefault(n, []).append(e - s)
        prog = max(per_mod, key=lambda k: sum(per_mod[k])) if per_mod else None
        for n, s, e in ops:
            lo, hi = max(s, t0), min(e, t1)
            if hi > lo:
                ops_total[n] = ops_total.get(n, 0.0) + (hi - lo) * 1e-9
        chips.append({
            "plane": plane,
            "busy_ns": length(busy),
            "program": prog,
            "program_ns": sum(per_mod[prog]) if prog else 0.0,
            "program_runs": len(per_mod[prog]) if prog else 0,
            "collective_ns": length(coll),
            "collective_exposed_ns": exposed,
            "gaps": [(a[1], b[0]) for a, b in zip([(t0, t0)] + busy, busy + [(t1, t1)])
                     if b[0] > a[1]],
        })
    if not chips:
        return None
    n = len(chips)
    return {
        "window_ns": t1 - t0,
        "rounds": len(m) - 1,
        "marks": m,
        "chips": chips,
        "ops": {k: v / n for k, v in ops_total.items()},
    }


def label_gap(start, end, marks_ns: dict[int, float], monitor_every: int) -> str:
    """What the launcher loop was doing in an idle gap of the device."""
    inside = [r for r, t in marks_ns.items() if start <= t <= end]
    if not inside:
        return "launcher loop, unattributed"
    r = inside[0]
    before = ("NetworkMonitor.step refresh, loss read-back, EMAs"
              if r % monitor_every == 0 else "loss read-back, EMAs, logging")
    return (f"end of round {r - 1} ({before}) then start of round {r} "
            "(batch staging, gossip draw, dispatch)")


def breakdown(red: dict, monitor_every: int, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["chips"][0]["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[label_gap(s, e, red["marks"], monitor_every), (e - s) * 1e-9]
                      for s, e in gaps],
    }
