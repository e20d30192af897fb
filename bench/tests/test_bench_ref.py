"""The reference planner plans as the program's float64 planner does:
the same fraction and the same node states, node for node, at a small
scale of each configuration."""
import json
from pathlib import Path

import pytest

from bench import check, sut
from bench.gen import tpch
from bench.ref import advise
from bench.ref.estimate import Reference, tables_from

sut.add_program_path()

from repro.core import AdvisorOptions, DesignAdvisor  # noqa: E402
from repro.core.estimation_graph import EstimationPlanner  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("name", ["tpch_sf1", "tpch_sf1_z1"])
@pytest.mark.parametrize("insert_weight", [0.1, 20.0])
def test_reference_plan_is_the_programs_float64_plan(name, insert_weight):
    config = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    config["scale"] = 0.2
    data = tpch.make(config)
    stmts = tpch.workload(data, insert_weight)
    program = sut.schema(data)
    adv = DesignAdvisor(sut.workload(program, stmts),
                        AdvisorOptions(backend="numpy"))
    targets = list(adv.estimation_targets(adv._candidate_universe()[2]))
    acc = config["accuracy"]
    want = check.plain_plan(EstimationPlanner(
        program.tables, use_engine=False).plan(targets, acc["e"], acc["q"]))

    ref = Reference(tables_from(data))
    ref_targets = advise.universe(stmts, ref.tables).targets
    got = ref.plan(ref_targets, acc["e"], acc["q"])
    assert got.targets == want.targets
    assert got.f == want.f
    assert got.nodes == want.nodes
