"""The comparison that decides `correct`: the program's answers, as the
timed path produced them, against the plain reference (`bench.ref`).

Per checked request two numbers, each held to the configuration's
limit:

* `size_rel_gap` — the largest relative gap between an estimation
  target's registered size and the reference's (1 for a target the
  program left without a size).  The reference plans on its own (the §5.2
  greedy in float64 over the same fraction grid, `bench.ref.plan`) and
  sizes every target under its plan: SampleCF where it samples, the §4.2
  deduction it chose where it deduces.  A program that plans otherwise
  (another fraction, a deduction where the reference samples) registers
  other sizes, and so does one whose codecs or deductions are wrong.  Two
  plans may differ where the program's float32 scores tie and the
  reference's float64 scores do not, between ColExt partitions whose
  deductions agree: those move the gap by float64 rounding alone.
* `cost_rel_gap` — the program's recommendation against the
  reference's own (float64 what-if costing and the same greedy over the
  reference's sizes): the larger of the gap between the cost the program
  reports and the reference's cost, and the excess of the reference's
  cost of the program's configuration over it, relative.  A cost that
  costing got wrong, or a configuration that enumeration got wrong, both
  move it; float32 ties between equal-cost configurations do not.

The log line of each request also says whether the two plans are the
same node for node (`same_plan`), and their fractions.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.ref import advise
from bench.ref.estimate import Key, PlainPlan, Reference

NUMBERS = ("size_rel_gap", "cost_rel_gap")


def plain_plan(plan) -> PlainPlan:
    """The program's estimation plan as plain data."""
    def key(k) -> Key:
        return Key(k.table, tuple(k.cols), k.method)
    nodes = {}
    for k, node in plan.nodes.items():
        state = node.state.name
        if state == "SAMPLED":
            nodes[key(k)] = ("SAMPLED", ())
        elif state == "DEDUCED":
            nodes[key(k)] = (node.chosen.kind,
                             tuple(key(c) for c in node.chosen.children))
        elif state != "NONE":
            nodes[key(k)] = (state, ())
    return PlainPlan(plan.f, tuple(key(k) for k in plan.targets), nodes)


def plain_config(config) -> frozenset:
    if isinstance(config, frozenset):
        return config
    return frozenset(advise.Index(i.table, tuple(i.cols), i.compression,
                                  i.clustered) for i in config.indexes)


def reference_sizes(ref: Reference, statements: List, accuracy: dict
                    ) -> Tuple[List[Key], PlainPlan, Dict[Key, float]]:
    """The workload's targets, the reference's plan, its sizes."""
    targets = advise.universe(statements, ref.tables).targets
    plan = ref.plan(targets, accuracy["e"], accuracy["q"])
    return targets, plan, ref.resolve(plan)


def compare(ref: Reference, statements: List, budget: float,
            accuracy: dict, prog_plan, prog_sizes: Dict[Tuple, float],
            prog_config, prog_cost: float) -> Dict[str, float]:
    """The numbers for one answer (see the module docstring), and for the
    log whether the plans and the configurations were the same."""
    targets, plan, resolved = reference_sizes(ref, statements, accuracy)
    sizes = advise.Sizes(ref.tables)
    size_gap = 0.0
    for k in targets:
        want = resolved[k]
        sizes.registered[(k.table, k.cols, k.method)] = want
        got = prog_sizes.get((k.table, k.cols, k.method, None))
        gap = 1.0 if got is None else abs(got - want) / max(abs(want), 1.0)
        size_gap = max(size_gap, gap)
    config = plain_config(prog_config)
    ref_of_prog = advise.Optimizer(statements, sizes).workload_cost(config)
    ref_config, ref_cost = advise.recommend(statements, ref.tables, sizes,
                                            budget)
    cost_gap = float(max(abs(prog_cost - ref_cost), ref_of_prog - ref_cost)
                     / max(abs(ref_cost), 1e-300))
    pp = plain_plan(prog_plan) if prog_plan is not None else None
    return {"size_rel_gap": size_gap, "cost_rel_gap": cost_gap,
            "same_plan": pp is not None and pp.f == plan.f
            and pp.nodes == plan.nodes,
            "f": plan.f, "program_f": pp.f if pp is not None else None,
            "same_config": config == ref_config}


def control_answer(ctrl: Reference, statements: List, budget: float,
                   accuracy: dict) -> Tuple[object, Dict[Tuple, float],
                                            frozenset, float]:
    """The control in the program's place: the reference at bfloat16
    sample precision answers the same request."""
    targets, _, resolved = reference_sizes(ctrl, statements, accuracy)
    sizes = advise.Sizes(ctrl.tables)
    out: Dict[Tuple, float] = {}
    for k in targets:
        sizes.registered[(k.table, k.cols, k.method)] = resolved[k]
        out[(k.table, k.cols, k.method, None)] = resolved[k]
    config, cost = advise.recommend(statements, ctrl.tables, sizes, budget)
    return None, out, config, cost


def worst(rows: List[Dict[str, float]]) -> Dict[str, float]:
    return {n: max((r[n] for r in rows), default=float("nan"))
            for n in NUMBERS}
