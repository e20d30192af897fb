"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in `BENCHMARK.json`: its configuration file
(`bench/configs/<config>.json`), whose `generator` names the data
generator (`bench/gen/<generator>.py`); its traffic mix
(`bench/traffic/<traffic>.json`), whose `kind` names the request loop
(`bench/kinds/<kind>.py`); and, with `--trace 1`, one reader per
per-layer metric (`bench/metrics/<name>.py`).  Adding a cell, a
configuration, a generator, a mix, a kind or a metric adds files and
entries; no existing file changes.

A run: generate the configuration's data from its own seed and the
traffic from `--seed`; warm up on requests of another stream of the
seed (set-up); drive the advisor on the paths its configuration names
(`advisor`) for `--seconds` in a closed loop; read the
device's peak memory; compare a seeded sample of the window's answers,
and the slowest, with the plain reference (`bench.check`); print the
compared numbers beside their limits on standard error and, as the last
line of standard output, one JSON object.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import os
import time

T_START = time.perf_counter()

# one process with few threads: the host's numerical libraries stay on
# one thread each, so that runs share the host's cores alike
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench_out" / "trace"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(root: Path, workload: str):
    """(benchmark, cell, configuration, traffic) by the cell's name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def load_module(root: Path, package: str, name: str):
    """`bench/<package>/<name>.py` of this checkout, as the module
    `bench.<package>.<name>` (one module object per name, so that the
    plain data types it shares with the reference stay one type)."""
    modname = f"bench.{package}.{name}"
    mod = sys.modules.get(modname)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            modname, root / "bench" / package / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell_name: str, section: str) -> List[dict]:
    """The metrics of `section` that this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


class CompileCounter:
    """Programs lowered for compilation, from jax's own compile events."""

    def __init__(self):
        import jax
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == LOWERING_EVENT:
            self.names.append(str(kwargs.get("fun_name", "?")))


def quantile(xs: List[float], q: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else float("nan")


class Context:
    """What a per-layer metric reader reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


@dataclasses.dataclass
class Record:
    """One finished request: its inputs and the program's answer."""
    index: int
    seconds: float
    statements: List
    budget: float
    rec: object                    # the program's Recommendation
    sizes: Dict[Tuple, float]      # the program's registered sizes


class Window:
    """What one measured window produced."""

    def __init__(self):
        self.records: List = []
        self.attempted = 0
        self.failed = 0
        self.seconds = 0.0
        self.compiles: List[str] = []


class Cell:
    """One cell, set up: its configuration's data on the program's types,
    the advisor options, and the device.  `mix` makes and warms up the
    traffic, `window` measures it, `compare` checks its answers."""

    def __init__(self, root: Path, workload: str, require_tpu: bool = True,
                 overrides: Optional[dict] = None):
        self.root = root
        self.bench, self.cell, config, self.traffic = load_cell(root,
                                                                workload)
        self.config = dict(config, **(overrides or {}))
        if not (root / "src" / "repro").is_dir():
            raise FileNotFoundError(f"no program under {root / 'src'}")
        import jax
        devs = jax.devices()
        if require_tpu and (devs[0].platform != "tpu"
                            or len(devs) < self.cell["chips"]):
            raise NoChip(f"cell {workload} needs {self.cell['chips']} TPU "
                         f"chip(s); jax found {len(devs)} "
                         f"{devs[0].platform} device(s)")
        self.dev = devs[0]
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        from bench import sut
        sut.add_program_path()
        from repro.core import AdvisorOptions, backend
        if devs[0].platform == "tpu":
            # the cache at a fixed path inside the checkout, every program
            # in it, so that only a checkout's first run compiles
            backend.enable_compile_cache()
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            if backend.pallas_interpret():
                raise RuntimeError("Pallas would run in interpret mode on "
                                   "a TPU")
        self.compiles = CompileCounter()
        self.gen = load_module(root, "gen", self.config["generator"])
        self.data = self.gen.make(self.config)
        self.program_schema = sut.schema(self.data)
        acc = self.config["accuracy"]
        self.options = AdvisorOptions(**self.config["advisor"], e=acc["e"],
                                      q=acc["q"],
                                      sample_seed=self.config["sample_seed"])
        log(f"data: {time.perf_counter() - T_START:.3f}s")

    def mix(self, seed: int):
        """The seed's traffic, started and warmed up."""
        kind = load_module(self.root, "kinds", self.traffic["kind"])
        mix = kind.Mix(self.traffic["params"], self.data, seed, self.gen)
        mix.start(self.program_schema, self.options)
        for b in mix.warmup():
            log(f"warm-up block {b}: {time.perf_counter() - T_START:.3f}s, "
                f"compiles {self.compiles.count}")
        log(f"set-up done: {time.perf_counter() - T_START:.3f}s, compiles "
            f"{self.compiles.count}")
        return mix

    def window(self, mix, seconds: float, trace: bool = False,
               break_answers=None) -> Window:
        """Requests back to back until `seconds` have passed; the window
        ends when the last request started in it completes."""
        from bench import spans
        from repro.kernels import codec_bytes, planner_score
        import jax
        w = Window()
        w.stages = spans.StageTimes().install() if trace else None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            TRACE_DIR.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        codec0, planner0 = codec_bytes.counters(), planner_score.counters()
        compiles0 = self.compiles.count
        requests = mix.window()
        t_w0 = time.perf_counter()
        w.setup_s = t_w0 - T_START
        while time.perf_counter() - t_w0 < seconds:
            thunk = next(requests)
            w.attempted += 1
            t0 = time.perf_counter()
            try:
                if trace:
                    with spans.request_span(self.traffic["metric"]):
                        stmts, budget, rec, sizes = thunk()
                else:
                    stmts, budget, rec, sizes = thunk()
            except Exception as e:  # a request that raises is failed
                w.failed += 1
                print(f"request {w.attempted - 1} raised "
                      f"{type(e).__name__}: {e}", file=sys.stderr,
                      flush=True)
                continue
            if break_answers is not None:
                rec, sizes = break_answers(rec, sizes)
            w.records.append(Record(
                w.attempted - 1, time.perf_counter() - t0, stmts, budget,
                rec, sizes))
        w.seconds = time.perf_counter() - t_w0
        w.compiles = self.compiles.names[compiles0:]
        if trace:
            jax.profiler.stop_trace()
            w.stages.uninstall()
        w.codec = {k: codec_bytes.counters()[k] - codec0[k] for k in codec0}
        w.planner = {k: planner_score.counters()[k] - planner0[k]
                     for k in planner0}
        times = [r.seconds for r in w.records]
        log(f"window: {len(w.records)} of {w.attempted} requests in "
            f"{w.seconds:.3f}s; per request median "
            f"{statistics.median(times) if times else float('nan'):.4f}s "
            f"p90 {quantile(times, 0.9):.4f}s max "
            f"{max(times, default=0):.4f}s")
        log(f"compiles in window: {len(w.compiles)} "
            f"{sorted(set(w.compiles))}")
        return w

    def checked(self, w: Window, seed: int) -> List:
        """A sample of the window's answers drawn from the seed, and the
        slowest one."""
        import numpy as np
        if not w.records:
            return []
        n = len(w.records)
        rng = np.random.default_rng([seed, 3])
        picked = {int(i) for i in rng.choice(
            n, size=min(self.traffic["check_requests"], n), replace=False)}
        picked.add(max(range(n), key=lambda i: w.records[i].seconds))
        return [w.records[i] for i in sorted(picked)]

    def compare(self, checked: List, control: bool = False):
        """Worst numbers over the checked answers, and how many of them
        the limits reject.  With `control`, the control answers the same
        requests in the program's place."""
        from bench import check
        from bench.ref.estimate import Reference, tables_from
        t0 = time.perf_counter()
        ref = Reference(tables_from(self.data), self.config["sample_seed"])
        ctrl = (Reference(tables_from(self.data), self.config["sample_seed"],
                          control=True) if control else None)
        acc = self.config["accuracy"]
        rows = []
        for r in checked:
            if ctrl is None:
                ans = (r.rec.estimation_plan, r.sizes, r.rec.config,
                       r.rec.cost)
            else:
                ans = check.control_answer(ctrl, r.statements, r.budget, acc)
            row = check.compare(ref, r.statements, r.budget, acc, *ans)
            rows.append(row)
            log(f"{'control' if control else 'checked'} request {r.index} "
                f"({r.seconds:.3f}s): {row}")
        limits = self.config["limits"]
        rejected = sum(1 for row in rows
                       if any(row[n] > limits[n] for n in check.NUMBERS))
        log(f"reference: {len(rows)} requests in "
            f"{time.perf_counter() - t0:.3f}s")
        return check.worst(rows), rejected


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             break_answers=None) -> dict:
    """One run of one cell; returns the result object.

    `overrides` replaces configuration keys (tests run a cell at a tiny
    scale); `break_answers` is applied to every window answer before it is
    recorded (tests plant faults under the timed path)."""
    cell = Cell(root, workload, require_tpu, overrides)
    mix = cell.mix(seed)
    w = cell.window(mix, seconds, trace, break_answers)
    stats = cell.dev.memory_stats() or {}
    device = dict(cell.device,
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    result: Dict = {"correct": False, "attempted": w.attempted,
                    "failed": w.failed, "metrics": {}, "device": device}
    completed = len(w.records)
    metric = cell.traffic["metric"]
    if not trace:
        for m in cell_metrics(cell.bench, workload, "end_to_end"):
            if m["name"] == "setup_s":
                v = w.setup_s
            elif m["name"] == metric and completed:
                v = w.seconds / completed
            else:
                continue
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench import trace_reduce
        tr = trace_reduce.load(trace_reduce.find_xplane(str(TRACE_DIR)))
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = w.seconds
        ctx = Context(
            records=w.records, completed=completed, window_s=w.seconds,
            busy_s=device["busy_s"], trace=tr, stages=w.stages.snapshot(),
            codec=w.codec, planner=w.planner, schema=cell.data,
            device_kind=device["kind"])
        for m in cell_metrics(cell.bench, workload, "per_layer"):
            try:
                v = metric_reader(root, m["name"])(ctx)
            except Exception as e:  # a broken reader nulls its metric
                print(f"metric {m['name']}: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                v = None
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                               "idle_gaps": trace_reduce.idle_gaps(tr)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # --- the comparison, once the window has closed -----------------------
    checked = cell.checked(w, seed)
    del mix
    gc.collect()
    compared, rejected = cell.compare(checked)
    limits = cell.config["limits"]
    result["failed"] = w.failed + rejected
    result["correct"] = bool(
        checked and w.failed == 0 and rejected == 0
        and all(compared[n] <= limits[n] for n in compared))
    result["compared"] = {n: {"value": v, "limit": limits[n]}
                          for n, v in compared.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name}: {float(c['value'])!r} "
              f"(limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
