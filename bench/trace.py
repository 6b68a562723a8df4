"""Reduction of a profiler trace (`.xplane.pb`) to what the metrics read.

`load` reads the trace with `jax.profiler.ProfileData` and keeps three
things on one clock (nanoseconds from the trace's start):

* device ops: every event on each accelerator plane's "XLA Ops" line;
* programs: every event on its "XLA Modules" line (one per executed
  jitted program), named by the program;
* host spans: the harness's own `TraceAnnotation`s, whose names start
  with `bench.`.

The functions below turn those into busy time, per-program device time,
per-kernel device time and the `breakdown` of a result line: the device
ops that took most time, and the longest gaps in which no op ran, each
labelled by the innermost harness span that covers its middle (what the
host was doing).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re
from typing import Dict, List, Tuple

Interval = Tuple[int, int]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class Trace:
    ops: List[List[Tuple[str, int, int]]]       # per chip: (name, start, end)
    programs: List[List[Tuple[str, int, int]]]  # per chip
    spans: List[Tuple[str, int, int]]           # host: (name, start, end)
    planes: List[str]


def find(directory: str) -> str:
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, programs, spans, planes = [], [], [], []
    for plane in data.planes:
        planes.append(plane.name)
        if _device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops.append([(e.name, int(e.start_ns), int(e.end_ns))
                        for e in lines[OPS_LINE].events])
            mods = lines.get(MODULES_LINE)
            programs.append([(e.name, int(e.start_ns), int(e.end_ns))
                             for e in mods.events] if mods else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, programs, sorted(spans, key=lambda s: s[1]), planes)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(intervals: List[Interval], holes: List[Interval]
             ) -> List[Interval]:
    """`intervals` minus the union of `holes` (both sorted unions)."""
    out, holes = [], union(holes)
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def busy(trace: Trace, chip: int, lo: int, hi: int) -> List[Interval]:
    return clip(union([(s, e) for _, s, e in trace.ops[chip]]), lo, hi)


def span_intervals(trace: Trace, name: str) -> List[Interval]:
    return union([(s, e) for n, s, e in trace.spans if n == name])


def step_programs(trace: Trace, chip: int, lo: int, hi: int,
                  names: Tuple[str, ...]) -> List[Tuple[str, int, int]]:
    """The chip's executions inside [lo, hi] of programs whose name holds
    one of `names`, in the order they ran (the order they were
    dispatched)."""
    return [p for p in trace.programs[chip]
            if p[1] >= lo and p[2] <= hi and any(n in p[0] for n in names)]


HLO = re.compile(r"^%?(?P<inst>[\w.-]+) = (?P<shape>.*?) (?P<op>[\w-]+)\(")
CONTAINERS = ("while", "conditional", "call")


def opcode(name: str) -> str:
    """The HLO opcode of an op event named by its instruction text
    ('%fusion.3 = bf16[8]{0} fusion(...)' -> 'fusion'), else ''."""
    m = HLO.match(name)
    return m.group("op") if m else ""


def op_label(name: str) -> str:
    """A short stable label: instruction, result shape without layouts,
    opcode ('fusion.220 bf16[8,11008] fusion')."""
    m = HLO.match(name)
    if not m:
        return name[:80]
    shape = re.sub(r"\{[^{}]*\}", "", m.group("shape"))
    return f"{m.group('inst')} {shape[:60]} {m.group('op')}"


def ops_inside(trace: Trace, chip: int, spans: List[Interval],
               op: str, prefix: str = "") -> List[Interval]:
    """Intervals of the chip's ops with HLO opcode `op`, whose instruction
    name starts with `prefix`, that lie inside one of `spans` (sorted,
    disjoint)."""
    out, j = [], 0
    for name, s, e in sorted(trace.ops[chip], key=lambda o: o[1]):
        while j < len(spans) and spans[j][1] < s:
            j += 1
        if j < len(spans) and spans[j][0] <= s and e <= spans[j][1] \
                and opcode(name) == op and name.lstrip("%").startswith(prefix):
            out.append((s, e))
    return out


def top_ops(trace: Trace, lo: int, hi: int, top: int = TOP
            ) -> List[List]:
    """The ops that took most device time in [lo, hi], by instruction
    (containers such as a layer loop left out: their bodies are listed)."""
    acc: Dict[str, int] = collections.defaultdict(int)
    for ops in trace.ops:
        for name, s, e in ops:
            if s >= lo and e <= hi and opcode(name) not in CONTAINERS:
                acc[op_label(name)] += e - s
    n = max(len(trace.ops), 1)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, dur / n / 1e9] for name, dur in best]


def label(trace: Trace, t: int) -> str:
    """Innermost harness span covering time t, or 'none'."""
    best, width = "none", None
    for name, s, e in trace.spans:
        if s > t:
            break
        if e >= t and (width is None or e - s < width):
            best, width = name, e - s
    return best


def idle_gaps(trace: Trace, chip: int, lo: int, hi: int, top: int = TOP
              ) -> List[List]:
    """The longest gaps in [lo, hi] with no op on the chip, each with the
    harness span the host was in at its middle."""
    b = busy(trace, chip, lo, hi)
    gaps = subtract([(lo, hi)], b)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(trace, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:top]]


def matched(programs: List[Tuple[str, int, int]], calls: List[tuple]
            ) -> bool:
    """Whether the step programs pair one to one, in dispatch order, with
    the calls the harness recorded, each program (its name carries the
    compiled program's fingerprint) always with one kind of call: decode,
    prefill, or a megastep of one K."""
    if len(programs) != len(calls):
        return False
    kind_of: Dict[str, tuple] = {}
    for (name, _, _), call in zip(programs, calls):
        kind = (call[0],) + tuple(call[2:])
        if kind_of.setdefault(name, kind) != kind:
            return False
    return True


def step_ns(programs: List[Tuple[str, int, int]], calls: List[tuple],
            kinds: Tuple[str, ...]) -> Tuple[int, List[Interval]]:
    """Device time, and the intervals, of the step programs whose matching
    call (same position in dispatch order) is of one of `kinds`."""
    spans = [(s, e) for (_, s, e), call in zip(programs, calls)
             if call[0] in kinds]
    return total(spans), spans
