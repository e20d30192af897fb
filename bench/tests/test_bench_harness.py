"""The harness finds a cell's files by name, and never reports a result
without a chip."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import peaks, run

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_peaks_of_v5e():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


THROWAWAY_GEN = """
from bench.gen import QueryData, SchemaData, InsertData, tpch


def make(config):
    data = tpch.make(config)
    keep = ("orders", "customer")
    return SchemaData({t: data.tables[t] for t in keep},
                      (("orders", "o_custkey", "customer", "c_custkey"),))


def workload(schema, insert_weight):
    hi = schema.tables["orders"].minmax("o_totalprice")[1]
    return [QueryData("o1", "orders", (("o_totalprice", 0, hi // 3),),
                      ("o_custkey", "o_orderdate")),
            QueryData("c1", "customer", (("c_nationkey", 2, 4),),
                      ("c_acctbal",)),
            InsertData("load_orders", "orders", 50, weight=insert_weight)]
"""

THROWAWAY_KIND = """
import numpy as np

from bench.kinds import recommend


class Mix(recommend.Mix):
    \"\"\"Every request at the largest budget, weights redrawn.\"\"\"

    def _blocks(self, stream):
        rng = np.random.default_rng([self.seed, stream, 7])
        iw = max(self.p["insert_weights"])
        frac = max(self.p["budget_fractions"])
        while True:
            yield [self._request(rng, iw, frac)]
"""


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A throwaway configuration with a generator of its own, a mix of a
    kind of its own, and a metric, each a file of its own plus a
    BENCHMARK.json entry: the harness finds and runs them, and no file
    that was there changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    os.symlink(ROOT / "src", tmp_path / "src")
    before = _digests(tmp_path / "bench")

    config = json.loads((ROOT / "bench/configs/tpch_sf1.json").read_text())
    config.update(scale=0.2, z=0.5, generator="two_tables")
    (tmp_path / "bench/configs/throwaway.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/gen/two_tables.py").write_text(THROWAWAY_GEN)
    (tmp_path / "bench/kinds/largest_budget.py").write_text(THROWAWAY_KIND)
    (tmp_path / "bench/traffic/select_only.json").write_text(json.dumps({
        "kind": "largest_budget", "metric": "recommend_s",
        "check_requests": 1,
        "params": {"insert_weights": [0.1], "query_weight_range": [1, 3],
                   "budget_fractions": [0.2], "warmup_blocks": 2}}))
    (tmp_path / "bench/metrics/window_requests.test.py").write_text(
        "def read(ctx):\n    return float(ctx.completed)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "throwaway", "source": "test", "reduced": [],
        "file": "bench/configs/throwaway.json", "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.select_only", "config": "throwaway",
        "traffic": "select_only", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("throwaway.select_only")
    bench["per_layer"].append({
        "name": "window_requests.test", "unit": "requests",
        "better": "higher", "source": "program_counter", "layer": "test",
        "moves": "recommend_s", "workloads": ["throwaway.select_only"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    names = [m["name"] for m in run.cell_metrics(
        bench, "throwaway.select_only", "per_layer")]
    assert names == ["window_requests.test"]
    reader = run.metric_reader(tmp_path, "window_requests.test")
    assert reader(run.Context(completed=3)) == 3.0
    cell = run.Cell(tmp_path, "throwaway.select_only", require_tpu=False)
    assert set(cell.data.tables) == {"orders", "customer"}
    result = run.run_cell(tmp_path, "throwaway.select_only", seed=5,
                          seconds=1.0, trace=False, require_tpu=False,)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"recommend_s", "setup_s"}
    after = _digests(tmp_path / "bench")
    assert {p: d for p, d in after.items() if p in before} == before


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sf1.recommend",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert '"correct": true' not in proc.stdout
    assert "TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert '"correct": true' not in proc.stdout
