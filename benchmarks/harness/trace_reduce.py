"""From a profiler trace to device busy time, per-operation time,
collective time and idle gaps.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure (``jax.profiler.ProfileData``, nothing but JAX)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``reduce_trace`` works on that structure alone, so the recorded trace under
``benchmarks/tests/data/`` checks it without a chip.  All times of one
trace are on one clock, nanoseconds from the start of the profile.

What counts as what:

* a device is a plane called ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, its programs those of ``XLA Modules``
  (not counted twice: they only name the program an operation ran in);
* an operation is named ``<program>/<instruction> <result type>``, cut
  from the HLO text the trace gives (``short_op``), the program by its
  name and the last four digits of its fingerprint (``short_program``);
* busy is the union of the operations' intervals, cut to the window;
* an operation's time is its self time: an event that encloses others (a
  ``while``, a ``conditional``, a ``call``) is charged only what its
  children leave;
* a collective is an operation whose HLO name starts with one of
  ``COLLECTIVE_PREFIXES``; its exposed part is the time in which no other
  operation that encloses none (a leaf) ran on that device;
* the window is the host span ``WINDOW_ANNOTATION``, which the harness
  opens around the traced queries; without it, the extent of the device
  events;
* an idle gap is a stretch of the window with no operation on the busiest
  device; it is named by the innermost host span open at its middle on
  the thread that opened the window, and below that by the innermost span
  on any other host thread (the engine's workers, the runtime's).
"""

from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_ANNOTATION = "bench:traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
COLLECTIVE_PREFIXES = ("all-to-all", "all-reduce", "all-gather",
                       "collective-permute", "reduce-scatter",
                       "collective-broadcast", "ragged-all-to-all")
TOP_OPS = 10
TOP_GAPS = 5

Interval = Tuple[float, float]


def read_xplane(path: str) -> dict:
    """The trace file as the plain structure described above."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save_recorded(trace: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The points of ``a`` (disjoint, sorted) that lie in no interval of
    ``b`` (disjoint, sorted)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: List[list], lo: float, hi: float):
    """``[(name, self intervals, whole interval or None)]`` for the events
    of ONE line cut to ``[lo, hi]``: each event's interval less what the
    events nested in it cover, and for an event that encloses no other (a
    leaf) its whole interval."""
    cut = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            cut.append((a, b, name))
    cut.sort(key=lambda e: (e[0], -(e[1] - e[0])))
    out = []
    stack: List[list] = []   # [end, name, start, children intervals]

    def close(entry):
        end, name, start, children = entry
        out.append((name, subtract([(start, end)], union(children)),
                    None if children else (start, end)))

    for a, b, name in cut:
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3].append((a, min(b, stack[-1][0])))
        stack.append([b, name, a, []])
    while stack:
        close(stack.pop())
    return out


def is_collective(name: str) -> bool:
    base = name.lstrip("%")
    return base.startswith(COLLECTIVE_PREFIXES)


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


def short_op(name: str) -> str:
    """``%fusion.7 = s32[33554432]{0:T(1024)} fusion(...)`` as
    ``fusion.7 s32[33554432]``; any other name as it is (cut to 80)."""
    m = _HLO.match(name)
    if not m:
        return name.lstrip("%")[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def short_program(name: str) -> str:
    """``jit__lambda(2971158065528068751)`` as ``jit__lambda#8751``."""
    m = _PROGRAM.match(name)
    return f"{m.group(1)}#{m.group(2)[-4:]}" if m else name[:80]


def program_at(modules: List[list], at: float) -> str:
    """The program (an event of ``XLA Modules``) running at ``at``."""
    for name, start, dur in modules:
        if start <= at < start + dur:
            return short_program(name)
    return "?"


@dataclass
class ChipTime:
    index: int
    busy_s: float
    collective_s: float
    collective_exposed_s: float
    op_self_s: Dict[str, float]
    #: operation seconds (self time) by program, in the window
    program_s: Dict[str, float] = field(default_factory=dict)
    busy: List[Interval] = field(repr=False, default_factory=list)


@dataclass
class TraceSummary:
    window_s: float
    window: Interval
    chips: List[ChipTime]
    idle_gaps: List[Tuple[str, float]]

    @property
    def busiest(self) -> ChipTime:
        return max(self.chips, key=lambda c: c.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def device_ops(self, top: int = TOP_OPS) -> List[Tuple[str, float]]:
        ops = sorted(self.busiest.op_self_s.items(),
                     key=lambda kv: -kv[1])
        return [(n, s) for n, s in ops[:top]]


def _host_lines(trace: dict):
    for plane in trace["planes"]:
        if plane["name"].startswith(HOST_PLANE_PREFIX):
            for line in plane["lines"]:
                yield line


def find_window(trace: dict) -> Tuple[Optional[Interval], Optional[str]]:
    """The traced window's span and the name of the host line holding
    it."""
    for line in _host_lines(trace):
        for name, start, dur in line["events"]:
            if name == WINDOW_ANNOTATION:
                return (start, start + dur), line["name"]
    return None, None


def _innermost(events: List[list], at: float) -> Optional[str]:
    best = None
    for name, start, dur in events:
        if dur > 0 and start <= at < start + dur and name != \
                WINDOW_ANNOTATION:
            if best is None or dur < best[1]:
                best = (name, dur)
    return best[0] if best else None


def name_gap(trace: dict, client_line: Optional[str], at: float) -> str:
    """What the host was doing at time ``at``."""
    on_client = None
    elsewhere = None
    for line in _host_lines(trace):
        found = _innermost(line["events"], at)
        if found is None:
            continue
        if line["name"] == client_line:
            on_client = found
        elif elsewhere is None:
            elsewhere = found
    parts = [p for p in (on_client, elsewhere) if p]
    return " > ".join(parts) if parts else "(no host span)"


def reduce_trace(trace: dict) -> TraceSummary:
    window, client_line = find_window(trace)
    devices = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        ops = [line["events"] for line in plane["lines"]
               if line["name"] == OPS_LINE]
        modules = [e for line in plane["lines"]
                   if line["name"] == MODULES_LINE for e in line["events"]]
        devices.append((int(m.group(1)), ops, modules))
    if not devices:
        raise ValueError(
            "the trace has no /device:TPU:<n> plane; planes: "
            f"{[p['name'] for p in trace['planes']]}")
    if window is None:
        starts = [e[1] for _, ops, _ in devices for ln in ops for e in ln]
        ends = [e[1] + e[2] for _, ops, _ in devices for ln in ops
                for e in ln]
        if not starts:
            raise ValueError("the trace holds no device operation")
        window = (min(starts), max(ends))
    lo, hi = window
    chips = []
    for index, ops, modules in sorted(devices, key=lambda d: d[0]):
        selfs = [s for events in ops for s in self_times(events, lo, hi)]
        busy = union([iv for _, ivs, _ in selfs for iv in ivs])
        op_self: Dict[str, float] = {}
        program_s: Dict[str, float] = {}
        coll, other = [], []
        for name, ivs, whole in selfs:
            if not ivs:
                continue
            program = program_at(modules, ivs[0][0])
            key = f"{program}/{short_op(name)}"
            op_self[key] = op_self.get(key, 0.0) + total(ivs) / 1e9
            program_s[program] = program_s.get(program, 0.0) + \
                total(ivs) / 1e9
            if is_collective(name):
                coll.extend(ivs)
            elif whole is not None:
                other.append(whole)
        coll_u, other_u = union(coll), union(other)
        chips.append(ChipTime(
            index=index, busy_s=total(busy) / 1e9,
            collective_s=total(coll_u) / 1e9,
            collective_exposed_s=total(subtract(coll_u, other_u)) / 1e9,
            op_self_s=op_self, program_s=program_s, busy=busy))
    busiest = max(chips, key=lambda c: c.busy_s)
    named: Dict[str, float] = {}
    for a, b in subtract([(lo, hi)], busiest.busy):
        label = name_gap(trace, client_line, (a + b) / 2)
        named[label] = named.get(label, 0.0) + (b - a) / 1e9
    gaps = sorted(named.items(), key=lambda kv: -kv[1])[:TOP_GAPS]
    return TraceSummary(window_s=(hi - lo) / 1e9, window=window,
                        chips=chips, idle_gaps=gaps)
