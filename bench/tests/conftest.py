"""A checkout root whose BENCHMARK.json also holds the cell that PERF.md
keeps for a later PR (`sf1z1.recommend`), so that its configuration
stays rehearsed on the CPU."""
import json
import os
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

LATER_CELLS = [
    {"name": "sf1z1.recommend", "config": "tpch_sf1_z1",
     "traffic": "recommend", "chips": 1, "why": "kept for a later PR"},
]


@pytest.fixture(scope="session")
def later_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    os.symlink(ROOT / "bench", root / "bench")
    os.symlink(ROOT / "src", root / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not any(c["name"] == "tpch_sf1_z1" for c in bench["configs"]):
        bench["configs"].append({
            "name": "tpch_sf1_z1", "source": "test", "reduced": [],
            "file": "bench/configs/tpch_sf1_z1.json", "why": "test"})
    have = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [c for c in LATER_CELLS if c["name"] not in have]
    for m in bench["end_to_end"]:
        if m["name"] == "recommend_s":
            m["workloads"] = sorted(set(m["workloads"])
                                    | {"sf1z1.recommend"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
