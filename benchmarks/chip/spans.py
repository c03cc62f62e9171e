"""The program's own spans and scopes in a profiler trace.

Works on the rows of ``xplane.read_rows`` and on a map from op name to
its shares of the named scopes (``op_scopes``, from the compiled round
program's text), and
gives three things over the window that the round marks bound:

- ``host``: the launcher loop's spans (``SPANS``) by name, clipped to the
  window: how many overlap it and their seconds in it;
- ``scopes``: device *self* time by named scope (``SCOPES``), mean over
  chips.  Ops nest on a device's op line (a ``while`` holds the ops of its
  body), so each instant of busy time goes to the innermost op running
  then, the one that started last, and nothing is counted twice.  That
  op's time is shared among scopes as ``op_scopes`` says; ops outside
  every scope go under ``UNSCOPED``;
- ``idle_by_span``: each idle instant of chip 0 given to the innermost
  program span open on the loop's thread (the line that holds the round
  marks) at that instant, or to ``OUTSIDE``.
"""

from __future__ import annotations

import heapq
import re

from xplane import OPS_LINE, MARK, marks, op_name

SPANS = ("round", "init", "compile", "stage", "draw", "dispatch", "device_wait",
         "readback", "ema", "monitor", "log", "checkpoint", "gc")
SCOPES = ("forward_backward", "optimizer", "gossip_pull", "gossip_mix")
UNSCOPED = "unscoped"
OUTSIDE = "outside any span"

# A scope is one component of an op's name stack, bare or wrapped by a
# transformation: ``jit(train_step)/forward_backward/while/body/...`` or
# ``.../transpose(jvp(forward_backward))/...``.
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=$|[/)])")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_META = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_ARRAY = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def scope_of(name_stack: str) -> str:
    """The outermost named scope in an op's name stack, or ``UNSCOPED``."""
    m = _SCOPE.search(name_stack)
    return m.group(1) if m else UNSCOPED


def _nbytes(shape: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        n = _BYTES.get(dtype, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def _closing(s: str, i: int) -> int:
    """Index of the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for k in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[k], 0)
        if depth == 0:
            return k
    return len(s)


def _parse(hlo_text: str) -> dict[str, list[dict]]:
    """Computation name -> its instructions in order, each a dict of
    ``name``, ``root``, ``shape``, ``opcode``, ``operands``, ``scope``
    (None without metadata) and ``calls``."""
    comps: dict[str, list[dict]] = {}
    cur: list[dict] = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            h = _HEAD.match(line)
            if h:
                cur = comps.setdefault(h.group(1), [])
            continue
        rest = line[m.end():]
        if rest.startswith("("):
            k = _closing(rest, 0) + 1
            shape, rest = rest[:k], rest[k:].lstrip()
        else:
            shape, _, rest = rest.partition(" ")
        opcode, _, args = rest.partition("(")
        args = args[:_closing("(" + args, 0) - 1]
        meta, calls = _META.search(line), _CALLS.search(line)
        cur.append({
            "name": m.group(2), "root": bool(m.group(1)), "shape": shape,
            "opcode": opcode.strip(),
            "operands": [a.split()[-1].split("*/")[-1].lstrip("%")
                         for a in args.split(", ") if a.strip()],
            "scope": scope_of(meta.group(1)) if meta else None,
            "calls": calls.group(1) if calls else None,
        })
    return comps


def _fusion_shares(body: list[dict], own: str) -> dict[str, float]:
    """Scope -> share of a fusion, by the bytes each scope reads and writes
    in it: a parameter's bytes go to the scopes of the instructions that use
    it (looking past instructions with no metadata), an output's to the
    scopes that produce it; bytes with no scope go to ``own``."""
    users: dict[str, list[str]] = {}
    for ins in body:
        for o in ins["operands"]:
            users.setdefault(o, []).append(ins["name"])
    up: dict[str, set] = {}
    for ins in body:
        up[ins["name"]] = ({ins["scope"]} if ins["scope"] else
                           set().union(*(up.get(o, set()) for o in ins["operands"])))
    down: dict[str, set] = {}
    for ins in reversed(body):
        own_scope = ins["scope"] if ins["opcode"] != "parameter" else None
        down[ins["name"]] = ({own_scope} if own_scope else
                             set().union(*(down.get(u, set())
                                           for u in users.get(ins["name"], ()))))
    by_name = {ins["name"]: ins for ins in body}
    weight: dict[str, float] = {}

    def give(nbytes, scopes):
        scopes = scopes or {own}
        for s in scopes:
            weight[s] = weight.get(s, 0.0) + nbytes / len(scopes)

    for ins in body:
        if ins["opcode"] == "parameter":
            give(_nbytes(ins["shape"]), down[ins["name"]])
    root = next((ins for ins in body if ins["root"]), body[-1])
    outs = ([by_name[o] for o in root["operands"] if o in by_name]
            if root["opcode"] == "tuple" else [root])
    for ins in outs:
        give(_nbytes(ins["shape"]), up[ins["name"]])
    total = sum(weight.values())
    return {s: w / total for s, w in weight.items()} if total else {own: 1.0}


def op_scopes(hlo_text: str) -> dict[str, dict[str, float]]:
    """Instruction name -> {scope: share of its time}, from the ``op_name``
    metadata of a compiled module's text.  An instruction belongs to the
    scope of its metadata (``UNSCOPED`` without).  A fusion holds
    instructions of several scopes when XLA fuses across them (the
    optimizer's update with the mix that follows it); being bound by memory,
    its time is shared by the bytes each scope moves (``_fusion_shares``)."""
    comps = _parse(hlo_text)
    out = {}
    for body in comps.values():
        for ins in body:
            own = ins["scope"] or UNSCOPED
            called = comps.get(ins["calls"]) if ins["opcode"] == "fusion" else None
            out[ins["name"]] = _fusion_shares(called, own) if called else {own: 1.0}
    return out


def innermost(intervals, t0: float, t1: float) -> list[tuple[float, float, str]]:
    """The union of ``(start, end, label)`` intervals within [t0, t1], as
    pieces ``(start, end, label)``, each labelled by the interval open there
    that started last (of two that start together, the shorter)."""
    ivs = sorted((s, e, lab) for s, e, lab in intervals if e > t0 and s < t1)
    bounds = sorted({t0, t1, *(max(s, t0) for s, _, _ in ivs),
                     *(min(e, t1) for _, e, _ in ivs)})
    heap: list = []
    out: list = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            s, e, lab = ivs[i]
            heapq.heappush(heap, (-s, e, i, lab))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        lab = heap[0][3]
        if out and out[-1][1] == a and out[-1][2] == lab:
            out[-1] = (out[-1][0], b, lab)
        else:
            out.append((a, b, lab))
    return out


def _loop_line(rows):
    for plane, line, name, *_ in rows:
        if name.startswith(MARK + "."):
            return plane, line
    return None


def reduce(rows, scopes: dict[str, dict[str, float]]) -> dict | None:
    """``host``, ``scopes`` and ``idle_by_span`` of the marked window (see
    the module's docstring), with ``window_ns`` and ``rounds``; None when
    there is no window or no device operation in it."""
    m = marks(rows)
    if len(m) < 2:
        return None
    t0, t1 = min(m.values()), max(m.values())
    loop = _loop_line(rows)
    host: dict[str, list] = {}
    program = []
    for plane, line, name, s, d in rows:
        if name not in SPANS or plane.startswith("/device:"):
            continue
        lo, hi = max(s, t0), min(s + d, t1)
        if hi > lo:
            c = host.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (hi - lo) * 1e-9
        if (plane, line) == loop:
            program.append((s, s + d, name))

    scope_s: dict[str, float] = {}
    idle = None
    chips = 0
    for plane in sorted({p for p, *_ in rows if p.startswith("/device:")}):
        ops = [(s, s + d, op_name(n)) for p, ln, n, s, d in rows
               if p == plane and ln == OPS_LINE]
        pieces = innermost(ops, t0, t1)
        if not pieces:
            continue
        chips += 1
        for s, e, op in pieces:
            for lab, share in scopes.get(op, {UNSCOPED: 1.0}).items():
                scope_s[lab] = scope_s.get(lab, 0.0) + (e - s) * 1e-9 * share
        if idle is None:
            edges = [(t0, t0)] + [(s, e) for s, e, _ in pieces] + [(t1, t1)]
            idle = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    if not chips:
        return None

    by_span: dict[str, float] = {}
    for a, b in idle:
        covered = 0.0
        for s, e, lab in innermost(program, a, b):
            by_span[lab] = by_span.get(lab, 0.0) + (e - s) * 1e-9
            covered += e - s
        if b - a > covered:
            by_span[OUTSIDE] = by_span.get(OUTSIDE, 0.0) + (b - a - covered) * 1e-9
    return {
        "window_ns": t1 - t0,
        "rounds": len(m) - 1,
        "host": {k: tuple(v) for k, v in host.items()},
        "scopes": {k: v / chips for k, v in scope_s.items()},
        "idle_by_span": sorted(([k, v] for k, v in by_span.items()), key=lambda kv: -kv[1]),
    }


def per_round_ms(ctx, kind: str, names) -> float | None:
    """Milliseconds a round of ``ctx.spans[kind]`` (``host`` or ``scopes``)
    summed over ``names``.  None without spans, or when the trace holds none
    of the program's spans (``host``) or named scopes (``scopes``), as for
    a program that has none; a name missing beside others reads 0."""
    s = getattr(ctx, "spans", None)
    if ctx.trace is None or s is None:
        return None
    got = s[kind]
    if not any(k in got for k in (SPANS if kind == "host" else SCOPES)):
        return None
    secs = sum((got[k][1] if kind == "host" else got[k]) for k in names if k in got)
    return 1e3 * secs / s["rounds"]
