"""Reduce a jax profiler trace (`.xplane.pb`) to device metrics.

Device planes are those named `/device:TPU:<n>`.  On each, the "XLA Ops"
line holds one event per device operation and the "XLA Modules" line one
event per executed program (a jitted function, a kernel call).  Busy
time is the union of the operation intervals, averaged over the device
planes; a kernel's device time is the summed duration of the modules
whose name matches it.  Host spans are the `bench.*` annotations that the
harness writes (`bench.spans`), used to say what the host was doing in
each idle gap.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operation events
    modules: Dict[str, List[Event]]    # device plane -> module events
    spans: List[Event]                 # host spans of the harness


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, spans)


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over device planes."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for events in trace.ops.values():
        total += sum(e - s for s, e in
                     union((ev.start_ns, ev.end_ns) for ev in events))
    return total / len(trace.ops) / 1e9


def module_s(trace: Trace, pattern: str) -> float:
    """Summed device seconds of the modules whose name matches."""
    rx = re.compile(pattern)
    return sum(ev.dur_ns for events in trace.modules.values()
               for ev in events if rx.search(ev.name)) / 1e9


HLO_NAME = re.compile(r"^(%\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def op_label(name: str) -> str:
    """An HLO operation's name and first result shape (`%sort.8
    s32[28160,273]`) out of the trace's full instruction text."""
    m = HLO_NAME.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, by name and shape:
    [[label, seconds]]."""
    by: Dict[str, float] = {}
    for events in trace.ops.values():
        for ev in events:
            key = op_label(ev.name)
            by[key] = by.get(key, 0.0) + ev.dur_ns
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def _span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost harness span covering host time t."""
    best: Optional[Event] = None
    for sp in spans:
        if sp.start_ns <= t < sp.end_ns and (best is None
                                             or sp.dur_ns < best.dur_ns):
            best = sp
    return best.name if best is not None else "outside any span"


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Device idle time between operations, summed by the span the host
    was in at each gap's midpoint: [[span, seconds]], longest first."""
    by: Dict[str, float] = {}
    for events in trace.ops.values():
        busy = union((ev.start_ns, ev.end_ns) for ev in events)
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            key = _span_at(trace.spans, (e0 + s1) / 2)
            by[key] = by.get(key, 0.0) + (s1 - e0)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
