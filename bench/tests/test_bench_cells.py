"""Each cell's warm-up, window and comparison, rehearsed at a tiny scale
on the CPU (Pallas in interpret mode), with the cells PERF.md keeps for
a later PR.  Correct here says the harness and the reference agree with
the program's logic; it says nothing of the chip."""
import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", sorted(set(CELLS) | {"sf1z1.recommend"}))
def test_cell_rehearsal_is_correct(name, later_root):
    result = run.run_cell(later_root, name, seed=2 ** 31 + 17, seconds=1.5,
                          trace=False, require_tpu=False,
                          overrides={"scale": 0.2})
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "compared"
    for c in result["compared"].values():
        assert c["value"] <= c["limit"]
