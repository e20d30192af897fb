"""Plain reference of the advisor's estimation planner (paper §5.1, §5.2,
App. C): the error model, the greedy state assignment over the deduction
graph, and the outer loop over sampling fractions.

A frozen, standalone copy of the advisor's scalar planner
(`EstimationPlanner.greedy_scalar` and `plan`), float64 throughout, with
`math.erf` for the normal probabilities.  It imports nothing of the
program.  Its plan decides which targets are sampled, at which fraction,
and which are deduced from which children; `Reference.resolve` then
sizes every node under it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

from bench.ref.estimate import (ORDER_DEPENDENT, PlainPlan, Key, RefTable,
                                rows_per_page)

F_GRID = (0.01, 0.025, 0.05, 0.075, 0.10)

# App. C fits: SampleCF bias and std per unit of -ln f (order-independent
# NS, order-dependent LDICT), deduction errors per extrapolated index.
SAMPLECF_FIT = {False: (0.0, 0.0062), True: (0.08, 0.055)}
COLSET_RV = (1.0, 0.0003)
COLEXT_FIT = {False: (0.01, 0.002), True: (-0.03, 0.01)}
EXACT_RV = (1.0, 0.0)

RV = Tuple[float, float]          # (mean, std) of estimate / true size


def samplecf_rv(method: str, f: float) -> RV:
    """The bias-corrected SampleCF error: mean 1, std shrunk by E[X]."""
    bias, std = SAMPLECF_FIT[ORDER_DEPENDENT[method]]
    lf = -math.log(max(min(f, 1.0), 1e-9))
    return (1.0, std * lf / (1.0 + bias * lf))


def colext_rv(method: str, a: int) -> RV:
    bias, std = COLEXT_FIT[ORDER_DEPENDENT[method]]
    return (1.0 + bias * a, std * a)


def compose(rvs: Sequence[RV]) -> RV:
    """Product of independent errors (Goodman's variance)."""
    e_prod = v_term = e2_term = 1.0
    for mean, std in rvs:
        e_prod *= mean
        v_term *= std * std + mean * mean
        e2_term *= mean * mean
    return (e_prod, math.sqrt(max(v_term - e2_term, 0.0)))


def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def prob_within(rv: RV, e: float) -> float:
    """P(1/(1+e) <= X <= 1+e) for X normal with the error's moments."""
    mean, std = rv
    lo, hi = 1.0 / (1.0 + e), 1.0 + e
    if std <= 1e-12:
        return 1.0 if lo <= mean <= hi else 0.0
    return _phi((hi - mean) / std) - _phi((lo - mean) / std)


def sampling_cost(table: RefTable, k: Key, f: float) -> float:
    """Pages of the index built on the sample (§5.1)."""
    n = max(2, int(round(table.nrows * f)))
    rpp = rows_per_page(sum(table.width[c] for c in k.cols))
    return float(-(-n // rpp))


def colext_deductions(k: Key) -> List[Tuple[str, Tuple[Key, ...]]]:
    """ColExt partitions: all singletons; (prefix, last); (first, rest)."""
    cols = k.cols
    if len(cols) < 2:
        return []
    parts = {tuple((c,) for c in cols), (cols[:-1], (cols[-1],)),
             ((cols[0],), cols[1:])}
    return [("colext", tuple(Key(k.table, p, k.method) for p in ps))
            for ps in sorted(parts)]


class _Node:
    __slots__ = ("state", "chosen", "rv")

    def __init__(self):
        self.state = "NONE"
        self.chosen: Tuple[str, Tuple[Key, ...]] = ("", ())
        self.rv: RV = EXACT_RV


class Planner:
    def __init__(self, tables: Dict[str, RefTable]):
        self.tables = tables

    def _cost(self, k: Key, f: float) -> float:
        return sampling_cost(self.tables[k.table], k, f)

    def greedy(self, targets: Sequence[Key], f: float, e: float, q: float
               ) -> Tuple[PlainPlan, float, bool]:
        """One greedy walk at fraction f: (plan, sampling cost, feasible)."""
        nodes: Dict[Key, _Node] = {}
        by_set: Dict[Tuple[str, frozenset, str], List[Key]] = {}

        def ensure(k: Key) -> _Node:
            n = nodes.get(k)
            if n is None:
                n = nodes[k] = _Node()
                by_set.setdefault((k.table, frozenset(k.cols), k.method),
                                  []).append(k)
            return n

        def known(k: Key) -> bool:
            return nodes[k].state != "NONE"

        def ded_rv(t: Key, d, trial: Dict[Key, RV]) -> RV:
            kind, children = d
            drv = COLSET_RV if kind == "colset" else colext_rv(
                t.method, len(children))
            return compose(tuple(trial.get(c, nodes[c].rv)
                                 for c in children) + (drv,))

        for t in targets:
            ensure(t)
        total = 0.0
        feasible = True
        used_as_child: set = set()
        for t in sorted(targets, key=lambda k: (len(k.cols), k.cols)):
            node = nodes[t]
            if known(t):
                continue
            mates = by_set.get((t.table, frozenset(t.cols), t.method), ())
            cands = ([] if ORDER_DEPENDENT[t.method] else
                     [("colset", (m,)) for m in mates if m.cols != t.cols])
            cands += colext_deductions(t)
            for _, children in cands:
                for c in children:
                    ensure(c)

            best, best_p = None, -1.0
            for d in cands:
                if all(known(c) for c in d[1]):
                    p = prob_within(ded_rv(t, d, {}), e)
                    if p >= q and p > best_p:
                        best, best_p = d, p
            if best is not None:
                node.state, node.chosen = "DEDUCED", best
                node.rv = ded_rv(t, best, {})
                used_as_child.update(best[1])
                continue

            best, best_cost = None, self._cost(t, f)
            for d in cands:
                unknown = [c for c in d[1] if not known(c)]
                if not unknown:
                    continue
                extra = sum(self._cost(c, f) for c in unknown)
                if extra >= best_cost:
                    continue
                trial = {c: samplecf_rv(c.method, f) for c in unknown}
                if prob_within(ded_rv(t, d, trial), e) >= q:
                    best, best_cost = d, extra
            if best is not None:
                for c in best[1]:
                    if not known(c):
                        nodes[c].state = "SAMPLED"
                        nodes[c].rv = samplecf_rv(c.method, f)
                        total += self._cost(c, f)
                node.state, node.chosen = "DEDUCED", best
                node.rv = ded_rv(t, best, {})
                used_as_child.update(best[1])
                continue

            node.state = "SAMPLED"
            node.rv = samplecf_rv(t.method, f)
            total += self._cost(t, f)
            if prob_within(node.rv, e) < q:
                feasible = False

        tset = set(targets)
        for k in sorted(list(nodes), key=lambda k: -len(k.cols)):
            if k in tset or k in used_as_child:
                continue
            if nodes[k].state == "SAMPLED":
                total -= self._cost(k, f)
            del nodes[k]
        for t in targets:
            if prob_within(nodes[t].rv, e) < q:
                feasible = False
        plain = {k: ("SAMPLED", ()) if n.state == "SAMPLED" else n.chosen
                 for k, n in nodes.items() if n.state != "NONE"}
        return PlainPlan(f, tuple(targets), plain), total, feasible

    def plan(self, targets: Sequence[Key], e: float, q: float
             ) -> PlainPlan:
        """The cheapest feasible greedy plan over the fraction grid, else
        the cheapest plan."""
        best = fallback = None
        for f in F_GRID:
            p, cost, feasible = self.greedy(targets, f, e, q)
            if feasible and (best is None or cost < best[1]):
                best = (p, cost)
            if fallback is None or cost < fallback[1]:
                fallback = (p, cost)
        return (best or fallback)[0]
