"""Traffic kind `fleet`: drifting tenants on one database, each in a
closed loop through one `AdvisorFleetService`.

`tenants` tenants are registered on one fleet of `slots` slots.  Each
starts from the configuration's workload (`gen.workload`, App. D.2 for
TPC-H) under a tenant prefix, with the insert weight of its share of
`insert_weights` (the first half of the tenants the first weight, and so
on) and each query's weight times a U(lo, hi) draw
(`query_weight_range`).

A tenant's round is one `WorkloadDelta` and one recommend.  The delta
replaces `moves` of the tenant's queries (drawn uniformly; a bulk load
is never moved) with as many fresh ad-hoc single-table queries, drawn
as the program's `make_scaled_workload` draws them (table weighted by
row count, 1 to 3 range or equality filters, 1 to 4 projected columns,
weight U(0.5, 2)), and multiplies the weight of `reweights` surviving
queries by U(lo, hi) (`reweight_range`).  A tenant's tables come in
blocks of `table_block` queries, a systematic sample of the row-count
weights in a random order, so that every seed puts nearly the same
share of its ad-hoc queries on each table.  The recommend that follows
asks for a budget of one of `budget_fractions` of the base design's
bytes; each tenant goes through all of them in an order drawn anew for
every `len(budget_fractions)` rounds, as the `recommend` kind's blocks
do.  Every tenant round makes the same number of moves, reweights and
recommends whatever the seed.

Closed loop per tenant: a tenant submits its next round as soon as its
recommend resolves, so every slot has work.  A window request returns
one resolved recommend as `(statements, budget, rec, sizes)`:
`statements` the tenant's workload as plain data, `sizes` the tenant
session's registered sizes.  The fleet is stepped only when no resolved
recommend waits, so at the window's end at most the recommends of the
last step (`slots` - 1) stay uncounted.

Warm-up first runs the fleet's `warm_up()` (where the program has it),
which runs once each kernel program a later round can use, then
`warmup_rounds` rounds per tenant drawn from another stream of the seed,
then `warm_up()` again for any sampling fraction the rounds met besides.
Every draw of a tenant comes from a stream of its own, so a tenant's
rounds do not depend on the order the fleet serves them in.

`window_counters()` is the change of the fleet's counters (`stats`)
since the current window began; the `*.fleet` metric readers read it.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, Iterator, List, Optional

import numpy as np

from bench import sut
from bench.gen import QueryData
from bench.kinds.recommend import base_bytes

TENANT_STREAM, WINDOW_STREAM, WARMUP_STREAM = 0, 1, 2

# the fleet and its counters when the current window began
_window: Dict[str, object] = {}


def window_counters() -> Optional[Dict[str, float]]:
    """The change of the fleet's numeric counters over the current window,
    or None before a window began."""
    if not _window:
        return None
    start, now = _window["start"], _window["fleet"].stats
    return {k: now[k] - v for k, v in start.items() if k in now}


def table_block(rng: np.random.Generator, schema, size: int) -> List[str]:
    """`size` tables for ad-hoc queries, in a random order: a systematic
    sample of the tables weighted by their rows (one uniform offset, then
    evenly spaced points on the weights' cumulative sum), so that each
    table's share of a block is its weight's to within one query."""
    names = list(schema.tables)
    w = np.array([schema.tables[n].nrows for n in names], dtype=np.float64)
    points = (rng.uniform() + np.arange(size)) / size
    picks = np.searchsorted(np.cumsum(w / w.sum()), points, side="right")
    return [names[min(int(i), len(names) - 1)]
            for i in rng.permutation(picks)]


def adhoc_query(rng: np.random.Generator, schema, table: str,
                name: str) -> QueryData:
    """One ad-hoc query on `table`, drawn as `make_scaled_workload` draws
    one: 1 to 3 filters (an equality with probability 1/4, else a range
    over 1% to 60% of the column's span), 1 to 4 projected columns among
    the rest."""
    t = schema.tables[table]
    cols = [c for c, _ in t.columns]
    nf = int(rng.integers(1, min(3, len(cols)) + 1))
    filters = []
    for ci in rng.choice(len(cols), size=nf, replace=False):
        col = cols[int(ci)]
        mn, mx = t.minmax(col)
        if mx <= mn or rng.random() < 0.25:
            v = int(rng.integers(mn, mx, endpoint=True))
            filters.append((col, v, v))
        else:
            frac = float(rng.uniform(0.01, 0.6))
            lo = int(rng.integers(mn, max(mn, int(mx - (mx - mn) * frac)),
                                  endpoint=True))
            hi = min(mx, lo + max(1, int((mx - mn) * frac)))
            filters.append((col, lo, hi))
    rest = [c for c in cols if c not in {f[0] for f in filters}]
    nu = int(rng.integers(1, min(4, max(1, len(rest))) + 1))
    used = ([rest[int(i)] for i in
             rng.choice(len(rest), size=min(nu, len(rest)), replace=False)]
            if rest else [filters[0][0]])
    return QueryData(name, t.name, tuple(filters), tuple(used),
                     weight=float(rng.uniform(0.5, 2.0)))


@dataclasses.dataclass
class Tenant:
    tid: str
    statements: List            # the tenant's workload, plain data
    rounds: int = 0             # rounds drawn so far, warm-up included
    budgets: List[float] = dataclasses.field(default_factory=list)
    tables: List[str] = dataclasses.field(default_factory=list)


class Mix:
    def __init__(self, params: dict, schema, seed: int, gen):
        self.p = params
        self.schema = schema
        self.base = base_bytes(schema)
        self.seed = seed
        n = params["tenants"]
        iws = params["insert_weights"]
        lo, hi = params["query_weight_range"]
        self.tenants: List[Tenant] = []
        for i in range(n):
            tid = f"t{i}"
            rng = np.random.default_rng([seed, TENANT_STREAM, i])
            stmts = gen.workload(schema, insert_weight=float(
                iws[i * len(iws) // n]))
            mult = rng.uniform(lo, hi, size=len(stmts))
            self.tenants.append(Tenant(tid, [
                dataclasses.replace(
                    s, name=f"{tid}_{s.name}",
                    weight=s.weight * (float(m) if isinstance(s, QueryData)
                                       else 1.0))
                for s, m in zip(stmts, mult)]))
        self.rngs: Dict[int, List[np.random.Generator]] = {
            s: [np.random.default_rng([seed, s, i]) for i in range(n)]
            for s in (WINDOW_STREAM, WARMUP_STREAM)}
        self.stream = WARMUP_STREAM
        self.ready: deque = deque()     # resolved, not yet handed out
        self.in_flight: Dict[str, tuple] = {}

    def start(self, program_schema, options) -> None:
        from repro.serve.advisor_service import (AdvisorFleetService,
                                                 FleetConfig)
        self.program_schema = program_schema
        self.options = options
        self.fleet = AdvisorFleetService(FleetConfig(slots=self.p["slots"]))
        for t in self.tenants:
            self.fleet.register_tenant(
                t.tid, sut.workload(program_schema, t.statements), options)

    # --- a tenant's round ----------------------------------------------
    def draw_round(self, t: Tenant, rng: np.random.Generator):
        """The tenant's next delta (plain data) and budget; applies the
        delta to the tenant's plain workload."""
        p = self.p
        if not t.budgets:
            fr = p["budget_fractions"]
            t.budgets = [float(fr[int(i)]) for i in rng.permutation(len(fr))]
        budget = t.budgets.pop(0) * self.base
        queries = [s.name for s in t.statements if isinstance(s, QueryData)]
        removed = [queries[int(i)] for i in
                   rng.choice(len(queries), size=p["moves"], replace=False)]
        while len(t.tables) < p["moves"]:
            t.tables += table_block(rng, self.schema, p["table_block"])
        added = [adhoc_query(rng, self.schema, t.tables.pop(0),
                             f"{t.tid}_r{t.rounds}_{j}")
                 for j in range(p["moves"])]
        survivors = [q for q in queries if q not in removed]
        lo, hi = p["reweight_range"]
        weight = {s.name: s.weight for s in t.statements}
        reweighted = [(survivors[int(i)],
                       weight[survivors[int(i)]] * float(rng.uniform(lo, hi)))
                      for i in rng.choice(len(survivors), size=p["reweights"],
                                          replace=False)]
        t.rounds += 1
        new_w = dict(reweighted)
        t.statements = [dataclasses.replace(s, weight=new_w[s.name])
                        if s.name in new_w else s
                        for s in t.statements if s.name not in removed] + added
        return removed, added, reweighted, budget

    def submit_round(self, i: int) -> None:
        from repro.core import WorkloadDelta
        t = self.tenants[i]
        removed, added, reweighted, budget = self.draw_round(
            t, self.rngs[self.stream][i])
        delta = self.fleet.submit_delta(t.tid, WorkloadDelta(
            added=tuple(sut.statement(s) for s in added),
            removed=tuple(removed), reweighted=tuple(reweighted)))
        rec = self.fleet.submit_recommend(t.tid, budget)
        self.in_flight[t.tid] = (i, list(t.statements), budget, delta, rec)

    def step(self) -> None:
        """One fleet step; every tenant whose recommend resolved hands
        its answer over and, in the window, submits its next round."""
        self.fleet.step()
        for tid, (i, stmts, budget, delta, rec) in list(
                self.in_flight.items()):
            if not rec.done():
                continue
            del self.in_flight[tid]
            err = delta.exception() or rec.exception()
            sizes = (None if err is not None else
                     dict(self.fleet.tenants[tid].session.sizes._sizes))
            self.ready.append((stmts, budget, err or rec.result(), sizes))
            if self.stream == WINDOW_STREAM:
                self.submit_round(i)

    def next_answer(self):
        while not self.ready:
            self.step()
        stmts, budget, rec, sizes = self.ready.popleft()
        if isinstance(rec, BaseException):
            raise rec
        return stmts, budget, rec, sizes

    # --- the harness's interface ---------------------------------------
    def warmup(self) -> Iterator[int]:
        """Runs the fleet's `warm_up()` (where the program has it), the
        warm-up rounds, yielding after each, and `warm_up()` again; ends
        with nothing in flight."""
        # every kernel program a later round can lower, loaded together
        # on the program's warm-up threads rather than one at a time as
        # the rounds first meet them; the second call adds the programs
        # of any fraction the rounds sampled at besides
        warm = getattr(self.fleet, "warm_up", None)
        block = 0
        if warm is not None:
            warm()
            yield block
            block += 1
        for r in range(self.p["warmup_rounds"]):
            for i in range(len(self.tenants)):
                self.submit_round(i)
            while self.in_flight:
                self.step()
            for _ in self.tenants:
                self.next_answer()
            yield block + r
        if warm is not None:
            warm()
        self.stream = WINDOW_STREAM
        for t in self.tenants:          # the window's blocks anew
            t.budgets.clear()
            t.tables.clear()

    def window(self) -> Iterator:
        _window.clear()
        _window.update(fleet=self.fleet, start=dict(self.fleet.stats))
        for i in range(len(self.tenants)):
            self.submit_round(i)
        while True:
            yield self.next_answer
