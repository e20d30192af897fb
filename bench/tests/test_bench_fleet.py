"""The `fleet` traffic kind and the `sf1.fleet` cell: rounds drawn from
the seed alone, the same moves in every round, the configuration's data
and layout, a planted fault, and the fleet counter readers."""
import json
from pathlib import Path

import pytest

from bench import run
from bench.gen import InsertData, QueryData, tpch
from bench.kinds import fleet

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "bench" / "configs"
TRAFFIC = json.loads((ROOT / "bench" / "traffic" / "fleet8.json").read_text())
PARAMS = TRAFFIC["params"]


@pytest.fixture(scope="module")
def data():
    config = json.loads((CONFIGS / "tpch_sf1_fleet8.json").read_text())
    return tpch.make(dict(config, scale=0.2))


def rounds(data, seed, n):
    """n window rounds of every tenant of the seed's mix, as plain data."""
    mix = fleet.Mix(PARAMS, data, seed, tpch)
    out = []
    for _ in range(n):
        for i, t in enumerate(mix.tenants):
            out.append((t.tid,) + mix.draw_round(
                t, mix.rngs[fleet.WINDOW_STREAM][i]))
    return mix, out


def test_rounds_are_deterministic_in_the_seed(data):
    big = 2 ** 31 + 12345
    assert rounds(data, big, 3)[1] == rounds(data, big, 3)[1]
    assert rounds(data, big, 3)[1] != rounds(data, big + 1, 3)[1]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77, 4_000_000_001])
def test_every_round_makes_the_same_moves(data, seed):
    start = fleet.Mix(PARAMS, data, seed, tpch)
    mix, drawn = rounds(data, seed, 6)
    assert len(mix.tenants) == PARAMS["tenants"]
    lo, hi = PARAMS["reweight_range"]
    budgets = {}
    for t0, t in zip(start.tenants, mix.tenants):
        loads = [s for s in t0.statements if isinstance(s, InsertData)]
        iw = PARAMS["insert_weights"][int(t.tid[1:]) * 2
                                      // PARAMS["tenants"]]
        assert [s.weight for s in loads] == [iw, iw]
        assert [s for s in t.statements if isinstance(s, InsertData)] \
            == loads
        assert len(t.statements) == len(t0.statements) == 22
    weights = {t.tid: {s.name: s.weight for s in t.statements}
               for t in start.tenants}
    for tid, removed, added, reweighted, budget in drawn:
        assert len(removed) == PARAMS["moves"] == len(added)
        assert len(reweighted) == PARAMS["reweights"]
        w = weights[tid]
        assert all(n in w and not n.startswith(f"{tid}_load")
                   for n in removed)
        for q in added:
            assert isinstance(q, QueryData) and q.name.startswith(tid)
            assert q.name not in w and 1 <= len(q.filters) <= 3
            assert 1 <= len(q.cols_used) <= 4
        for name, new in reweighted:
            assert name not in removed
            assert lo <= new / w[name] <= hi
        for name in removed:
            del w[name]
        w.update(reweighted)
        w.update((q.name, q.weight) for q in added)
        budgets.setdefault(tid, []).append(budget / mix.base)
    for t in mix.tenants:
        assert {s.name: s.weight for s in t.statements} == weights[t.tid]
    for got in budgets.values():
        for block in (got[:3], got[3:]):
            assert sorted(block) == sorted(PARAMS["budget_fractions"])


def test_ad_hoc_tables_come_in_row_weighted_blocks(data):
    """Every block of `table_block` ad-hoc queries of a tenant puts on
    each table its row-weighted share of the block, to within one
    query."""
    size = PARAMS["table_block"]
    rows = {n: t.nrows for n, t in data.tables.items()}
    total = sum(rows.values())
    for seed in (11, 2 ** 31 + 99):
        _, drawn = rounds(data, seed, size // PARAMS["moves"])
        by_tenant = {}
        for tid, _, added, _, _ in drawn:
            by_tenant.setdefault(tid, []).extend(q.table for q in added)
        for tables in by_tenant.values():
            assert len(tables) == size
            for name, n in rows.items():
                assert abs(tables.count(name) - size * n / total) < 1


def test_configuration_is_tpch_sf1_under_the_traffic_layout():
    sf1 = json.loads((CONFIGS / "tpch_sf1.json").read_text())
    fleet8 = json.loads((CONFIGS / "tpch_sf1_fleet8.json").read_text())
    extended = ("source", "deployment", "assumed", "guarantees")
    assert set(fleet8) == set(sf1)
    for key in sf1:
        if key not in extended:
            assert fleet8[key] == sf1[key], key
    for key in ("assumed", "guarantees"):
        assert sf1[key].items() <= fleet8[key].items()
    layout = fleet8["deployment"]
    assert layout["tenants"] == PARAMS["tenants"]
    assert layout["slots"] == PARAMS["slots"]
    assert layout["share_groups"] == 1 and layout["chips"] == 1
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == "sf1.fleet"]
    assert cell["chips"] == layout["chips"]
    assert cell["traffic"] == "fleet8" and TRAFFIC["kind"] == "fleet"


def _alter_cost(rec, sizes):
    import dataclasses
    return dataclasses.replace(rec, cost=rec.cost * (1 + 1e-3)), sizes


def test_an_altered_fleet_answer_comes_out_incorrect():
    result = run.run_cell(ROOT, "sf1.fleet", seed=5, seconds=1.0,
                          trace=False, require_tpu=False,
                          overrides={"scale": 0.2},
                          break_answers=_alter_cost)
    assert result["correct"] is False
    assert result["failed"] > 0


class _Fleet:
    def __init__(self, stats):
        self.stats = stats


READERS = ("prefetch_targets.fleet", "prefetch_hit.fleet", "cost_jobs.fleet")


def test_counter_readers_take_the_change_over_the_window(monkeypatch):
    read = {name: run.metric_reader(ROOT, name) for name in READERS}
    start = {"recommends": 8, "prefetch_targets": 100, "prefetch_hits": 40,
             "cost_prefetch_jobs": 30, "steps": 2}
    now = {"recommends": 24, "prefetch_targets": 140, "prefetch_hits": 200,
           "cost_prefetch_jobs": 94, "steps": 6}
    monkeypatch.setattr(fleet, "_window",
                        {"fleet": _Fleet(now), "start": start})
    ctx = run.Context(completed=9)
    assert read["prefetch_targets.fleet"](ctx) == 40 / 16
    assert read["prefetch_hit.fleet"](ctx) == pytest.approx(100 * 160 / 200)
    assert read["cost_jobs.fleet"](ctx) == 64 / 16
    # a program without the `recommends` counter, and no window: nothing
    del start["recommends"]
    assert read["prefetch_targets.fleet"](ctx) is None
    assert read["cost_jobs.fleet"](ctx) is None
    monkeypatch.setattr(fleet, "_window", {})
    assert all(read[n](ctx) is None for n in READERS)
