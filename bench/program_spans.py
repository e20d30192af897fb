"""The program's own spans (`repro.core.tracing`) in a traced window.

A per-layer metric that reads program spans reads `ctx.spans`: the spans
`tracing.drain()` returned after the window, one per stretch of work,
with its parent's id.  A name's total is the summed duration of its
spans; a span's self time is its duration less the part of it that its
child spans cover.

`bench/run.py --trace 1` does not turn the program's tracing on (PERF.md
§7 names the edit), so this module also runs a cell itself:

    python3 bench/program_spans.py --workload sf1.recommend --seed <n> \\
        --seconds <s>

sets the cell up and measures its window as `bench/run.py --trace 1`
does, with the program's tracing on in the window, checks the window's
answers as `bench/run.py` does, and prints one JSON object: the cell's
per-layer metrics and the span metrics (`SPAN_METRICS`), the totals
and self times per span name, how far the spans of the estimation stage
cover it, how far the program's stage spans agree with the harness's
stage timers, how far each span lies from its `repro.*` event in the
profiler's trace, and the device's idle gaps by the innermost span of
either kind.
"""
from __future__ import annotations

import heapq
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

PROGRAM_PREFIX = "repro."
OUTSIDE = "outside any span"
# the readers of `ctx.spans` (bench/metrics/), which no BENCHMARK.json
# entry names while `bench/run.py` hands its readers no spans
SPAN_METRICS = ("plan_ms.recommend", "planner_call_ms.recommend",
                "sample_ms.recommend", "permute_ms.recommend",
                "codec_call_ms.recommend", "samplecf_self_ms.recommend")


def totals_ns(spans: Sequence) -> Dict[str, int]:
    """Summed duration of the spans of each name."""
    out: Dict[str, int] = defaultdict(int)
    for s in spans:
        out[s.name] += s.end_ns - s.start_ns
    return dict(out)


def self_ns(spans: Sequence) -> Dict[str, int]:
    """Summed self time of the spans of each name: each span's duration
    less the union of its children's intervals within it."""
    children: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
    for s in spans:
        if s.parent_id is not None:
            children[s.parent_id].append((s.start_ns, s.end_ns))
    out: Dict[str, int] = defaultdict(int)
    for s in spans:
        covered = trace_reduce.union(
            (max(a, s.start_ns), min(b, s.end_ns))
            for a, b in children.get(s.span_id, ()))
        out[s.name] += (s.end_ns - s.start_ns) - sum(
            b - a for a, b in covered if b > a)
    return dict(out)


def per_recommend_ms(ctx, name: str, self_time: bool = False
                     ) -> Optional[float]:
    """Milliseconds in spans named `name` (their self time with
    `self_time`) per request completed in the window; None without
    completed requests or without such a span."""
    spans = getattr(ctx, "spans", None)
    if not ctx.completed or not spans:
        return None
    by = (self_ns if self_time else totals_ns)(spans)
    if name not in by:
        return None
    return by[name] / 1e6 / ctx.completed


def _span_keys(spans: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each of the increasing host times, the innermost span covering
    it: of the spans with start <= t < end, the shortest, the earliest
    listed among equals (as `trace_reduce._span_at`).  One sweep over the
    spans by start, with a heap of the open ones by (duration, index); a
    span that has ended is dropped when it reaches the top, and stays
    ended since the times increase."""
    order = sorted(range(len(spans)), key=lambda i: spans[i].start_ns)
    heap: List[Tuple[float, int]] = []
    k = 0
    keys = []
    for t in times:
        while k < len(order) and spans[order[k]].start_ns <= t:
            i = order[k]
            heapq.heappush(heap, (spans[i].dur_ns, i))
            k += 1
        while heap and spans[heap[0][1]].end_ns <= t:
            heapq.heappop(heap)
        keys.append(spans[heap[0][1]].name if heap else OUTSIDE)
    return keys


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """`trace_reduce.idle_gaps`, the same result, in one sweep over the
    spans per device plane in place of a scan of every span per gap."""
    by: Dict[str, float] = {}
    for events in trace.ops.values():
        busy = trace_reduce.union((ev.start_ns, ev.end_ns) for ev in events)
        gaps = list(zip(busy, busy[1:]))
        keys = _span_keys(trace.spans,
                          [(e0 + s1) / 2 for (_, e0), (s1, _) in gaps])
        for key, ((_, e0), (s1, _)) in zip(keys, gaps):
            by[key] = by.get(key, 0.0) + (s1 - e0)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def program_events(path: str) -> Tuple[int, List[Event]]:
    """The trace's start on the host's real-time clock (its
    `profile_start_time`, ns) and the program's `repro.*` host events, in
    ns after that start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    start = None
    events: List[Event] = []
    for plane in pd.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend(Event(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                              for e in line.events
                              if e.name.startswith(PROGRAM_PREFIX))
    if start is None:
        raise ValueError(f"no profile_start_time in {path}")
    return start, events


def clock_offsets_us(spans: Sequence, start_ns: int,
                     events: Sequence[Event]) -> Dict[str, float]:
    """Pairs each span with its trace event (by name, in start order) and
    gives the distances between their starts and between their ends, in
    microseconds: the median, the 99th percentile, the largest, how many
    spans lie more than 10 us from their event at either end, and the
    first 20 of those (`far`: name, end, distance in us, the span's
    place in start order)."""
    recorded = sorted((PROGRAM_PREFIX + s.name, s.start_ns - start_ns,
                       s.end_ns - start_ns) for s in spans)
    traced = sorted((e.name, e.start_ns, e.end_ns) for e in events)
    if [r[0] for r in recorded] != [t[0] for t in traced]:
        raise ValueError(f"{len(recorded)} spans but {len(traced)} "
                         f"{PROGRAM_PREFIX}* events, or other names")
    out: Dict = {"spans": len(recorded)}
    far = [False] * len(recorded)
    listed = []
    for end, col in (("start", 1), ("end", 2)):
        d = [abs(r[col] - t[col]) / 1e3 for r, t in zip(recorded, traced)]
        listed += [[r[0], end, x, i] for i, (r, x) in enumerate(
            zip(recorded, d)) if x > 10]
        far = [f or x > 10 for f, x in zip(far, d)]
        d.sort()
        for q, label in ((0.5, "p50"), (0.99, "p99"), (1.0, "max")):
            out[f"{end}_{label}_us"] = (d[min(len(d) - 1, int(q * len(d)))]
                                        if d else 0.0)
    out["over_10us"] = sum(far)
    out["far"] = listed[:20]
    return out


def measure(root: Path, workload: str, seed: int, seconds: float,
            require_tpu: bool = True, overrides: Optional[dict] = None
            ) -> dict:
    """One traced window of the cell with the program's tracing on, and
    its answers checked (`bench.run.Cell`; `overrides` as in
    `bench.run.run_cell`)."""
    import gc
    import shutil
    import time

    from bench import run
    cell = run.Cell(root, workload, require_tpu, overrides)
    from repro.core import tracing
    mix = cell.mix(seed)
    tracing.enable()
    try:
        w = cell.window(mix, seconds, trace=True)
    finally:
        tracing.disable()
    spans = tracing.drain()
    path = trace_reduce.find_xplane(str(run.TRACE_DIR))
    tr = trace_reduce.load(path)
    start_ns, events = program_events(path)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    completed = len(w.records)
    stages = w.stages.snapshot()
    ctx = run.Context(
        records=w.records, completed=completed, window_s=w.seconds,
        busy_s=trace_reduce.busy_s(tr), trace=tr, stages=stages,
        codec=w.codec, planner=w.planner, schema=cell.data,
        device_kind=cell.device["kind"], spans=spans)
    metrics = {}
    names = [m["name"] for m in run.cell_metrics(cell.bench, workload,
                                                   "per_layer")]
    for name in names + [n for n in SPAN_METRICS if n not in names]:
        try:
            v = run.metric_reader(root, name)(ctx)
        except Exception as e:  # a broken reader nulls its metric
            print(f"metric {name}: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            v = None
        if v is not None:
            metrics[name] = v
    total, own = totals_ns(spans), self_ns(spans)
    per = max(completed, 1) * 1e6
    estimate = total.get("advisor.estimate", 0)
    parts = ("estimate.plan", "estimate.sample", "estimate.samplecf",
             "estimate.resolve")
    costenum = total.get("advisor.cost", 0) + total.get("advisor.enumerate",
                                                        0)
    both = Trace(tr.ops, tr.modules, tr.spans + events)
    t0 = time.perf_counter()
    swept = idle_gaps(both, n=len(both.spans) + 1)
    sweep_s = time.perf_counter() - t0
    # the scan visits every span per gap: timed where that stays short
    gaps = sum(len(v) for v in tr.ops.values())
    scanned, scan_s = None, None
    if gaps * len(both.spans) <= 2e8:
        t0 = time.perf_counter()
        scanned = trace_reduce.idle_gaps(both, n=len(both.spans) + 1)
        scan_s = time.perf_counter() - t0
    idle = sum(v for _, v in swept)
    report = {
        "device": cell.device, "completed": completed,
        "window_s": w.seconds, "recommend_s": w.seconds / max(completed, 1),
        "metrics": metrics,
        "span_ms_per_recommend": {
            name: {"count": sum(1 for s in spans if s.name == name),
                   "total": total[name] / per, "self": own[name] / per}
            for name in sorted(total)},
        "estimate_covered": (sum(total.get(p, 0) for p in parts) / estimate
                             if estimate else None),
        "estimate_over_stage_timer": (
            estimate / 1e9 / stages["estimate"]
            if estimate and stages.get("estimate") else None),
        "cost_enumerate_over_stage_timer": (
            costenum / 1e9 / stages["costenum"]
            if costenum and stages.get("costenum") else None),
        "clock": clock_offsets_us(spans, start_ns, events),
        "idle_gaps": swept[:12],
        "idle_under_program_spans": (
            sum(v for name, v in swept if name.startswith(PROGRAM_PREFIX))
            / idle if idle else None),
        "idle_gaps_seconds": {"sweep": sweep_s, "scan": scan_s,
                              "same": None if scanned is None
                              else swept == scanned,
                              "spans": len(both.spans), "ops": gaps},
    }
    checked = cell.checked(w, seed)
    del mix
    gc.collect()
    compared, rejected = cell.compare(checked)
    limits = cell.config["limits"]
    report["failed"] = w.failed + rejected
    report["correct"] = bool(
        checked and w.failed == 0 and rejected == 0
        and all(compared[k] <= limits[k] for k in compared))
    report["compared"] = compared
    return report


def main(argv=None) -> int:
    import argparse
    import json

    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        report = measure(ROOT, args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench/program_spans.py: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
