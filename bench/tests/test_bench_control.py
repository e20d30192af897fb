"""The comparison fails what it must: the control (the reference at
bfloat16 sample precision in the program's place), and each fault a
cell can have, planted under the timed path, at a tiny scale."""
import dataclasses
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
TINY = {"scale": 0.2}


@pytest.mark.parametrize("name", ["sf1.recommend"])
def test_control_comes_out_incorrect(name):
    cell = run.Cell(ROOT, name, require_tpu=False, overrides=TINY)
    mix = cell.mix(seed=11)
    checked = cell.checked(cell.window(mix, 1.0), seed=11)
    limit = cell.config["limits"]["size_rel_gap"]
    worst, rejected = cell.compare(checked)
    assert rejected == 0 and worst["size_rel_gap"] <= limit
    worst, rejected = cell.compare(checked, control=True)
    assert rejected == len(checked) > 0
    assert worst["size_rel_gap"] > limit


def _alter_size(rec, sizes):
    k = sorted(sizes, key=repr)[0]
    return rec, {**sizes, k: sizes[k] + 1.0}


def _alter_cost(rec, sizes):
    return dataclasses.replace(rec, cost=rec.cost * (1 + 1e-3)), sizes


def _alter_config(rec, sizes):
    """The recommendation loses its last secondary index, or gains the
    base layout of a table back uncompressed."""
    sec = sorted((i for i in rec.config.indexes if not i.clustered),
                 key=lambda i: i.label())
    if sec:
        cfg = rec.config.remove(sec[-1])
    else:
        cl = sorted((i for i in rec.config.indexes if i.compression),
                    key=lambda i: i.label())[0]
        cfg = rec.config.replace(cl, cl.with_compression(None))
    return dataclasses.replace(rec, config=cfg), sizes


@pytest.mark.parametrize("fault", [_alter_size, _alter_cost, _alter_config],
                         ids=["size", "cost", "config"])
def test_an_altered_answer_comes_out_incorrect(fault):
    result = run.run_cell(ROOT, "sf1.recommend", seed=5, seconds=1.0,
                          trace=False, require_tpu=False,
                          overrides=TINY, break_answers=fault)
    assert result["correct"] is False
    assert result["failed"] > 0


def _half_fraction(plan, self, targets, e, q, f_grid):
    """The planner samples at half the fraction it chose."""
    return plan(self, targets, e, q, (plan(self, targets, e, q, f_grid).f
                                      / 2,))


def _loose_confidence(plan, self, targets, e, q, f_grid):
    """The planner deduces wherever a deduction reaches half the
    confidence asked for."""
    return plan(self, targets, e, q / 2, f_grid)


@pytest.mark.parametrize("fault", [_half_fraction, _loose_confidence],
                         ids=["smaller_f", "deduce_below_q"])
def test_a_planner_that_plans_otherwise_comes_out_incorrect(fault,
                                                            monkeypatch):
    """The timed path's planner chooses another plan; the program stays
    self-consistent under it, and the reference, planning on its own,
    sizes the targets otherwise."""
    from repro.core.estimation_graph import F_GRID, EstimationPlanner
    plan = EstimationPlanner.plan

    def planted(self, targets, e, q, f_grid=F_GRID):
        return fault(plan, self, targets, e, q, f_grid)

    monkeypatch.setattr(EstimationPlanner, "plan", planted)
    result = run.run_cell(ROOT, "sf1.recommend", seed=6, seconds=1.0,
                          trace=False, require_tpu=False, overrides=TINY)
    assert result["correct"] is False
    gap = result["compared"]["size_rel_gap"]
    assert gap["value"] > gap["limit"]
