"""Plain reference of the advisor's costing, candidate selection and
greedy enumeration (paper §6, App. A).

A frozen, standalone copy of the advisor's scalar numpy oracle: the
what-if cost model (`whatif.query_cost`, `cost_model`), per-query
candidates with skyline selection and merging (`candidates`), and the
backtracking greedy (`enumeration.greedy_enumerate_scalar`), with the
advisor's default options (methods NS and LDICT, skyline of at most 8
points, clustered candidates, backtracking).  Float64 throughout.  It
imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from bench.gen import InsertData, QueryData
from bench.ref.estimate import (Key, PAGE_BYTES, RefTable,
                                uncompressed_payload_bytes)

METHODS = ("NS", "LDICT")
ALPHA = {"NS": 1.0, "LDICT": 2.5}
BETA = {"NS": 0.20, "LDICT": 0.45}
MAX_SKYLINE_POINTS = 8
MAX_MERGES = 24
MAX_INDEXES = 64

# cost-model constants (ms), App. A
T_IO_SEQ = 0.08
T_IO_RAND = 5.0
CPU_ROW = 0.00005
ALPHA_UNIT = 0.0002
BETA_UNIT = 0.00002
INDEX_MAINT_CPU = 0.0005
SEEK_OVERHEAD = 1.0
DEFAULT_CF_PRIOR = 0.55


@dataclasses.dataclass(frozen=True)
class Index:
    table: str
    cols: Tuple[str, ...]
    compression: Optional[str] = None
    clustered: bool = False

    def label(self) -> str:
        c = f"^{self.compression}" if self.compression else ""
        cl = "*" if self.clustered else ""
        return f"{self.table}({','.join(self.cols)}){c}{cl}"

    def with_compression(self, m: Optional[str]) -> "Index":
        return dataclasses.replace(self, compression=m)


Config = FrozenSet[Index]


def for_table(config: Config, table: str) -> Tuple[Index, ...]:
    return tuple(sorted((i for i in config if i.table == table),
                        key=Index.label))


def clustered_of(config: Config, table: str) -> Optional[Index]:
    for i in config:
        if i.table == table and i.clustered:
            return i
    return None


def selectivity(table: RefTable, col: str, lo: int, hi: int) -> float:
    mn, mx = table.minmax(col)
    if mx <= mn:
        return 1.0
    frac = (min(hi, mx) - max(lo, mn) + 1) / (mx - mn + 1)
    return float(min(1.0, max(0.0, frac)))


def all_cols(q: QueryData) -> Tuple[str, ...]:
    seen = dict.fromkeys([c for c, _, _ in q.filters])
    seen.update(dict.fromkeys(q.cols_used))
    return tuple(seen)


class Sizes:
    """Index bytes: analytic when uncompressed, registered when compressed."""

    def __init__(self, tables: Dict[str, RefTable]):
        self.tables = tables
        self.registered: Dict[Tuple, float] = {}

    def size(self, idx: Index) -> float:
        t = self.tables[idx.table]
        usize = float(uncompressed_payload_bytes(
            t.nrows, [t.width[c] for c in idx.cols]))
        if idx.compression is None:
            return usize
        got = self.registered.get((idx.table, idx.cols, idx.compression))
        return got if got is not None else usize * DEFAULT_CF_PRIOR


def pages_of(size_bytes: float) -> float:
    return np.maximum(size_bytes, 0.0) / PAGE_BYTES


def beta(m: Optional[str]) -> float:
    return 0.0 if m is None else BETA[m] * BETA_UNIT


def scan_cost(size_bytes, nrows, ncols_used, compression):
    io = T_IO_SEQ * pages_of(size_bytes)
    return io + CPU_ROW * nrows + beta(compression) * nrows * ncols_used


def seek_cost(size_bytes, nrows_index, sel, ncols_used, compression):
    rows = nrows_index * sel
    io = SEEK_OVERHEAD + T_IO_SEQ * pages_of(size_bytes * sel)
    return io + CPU_ROW * rows + beta(compression) * rows * ncols_used


def rid_lookup_cost(nrows, base_size_bytes, base_compression, ncols_used):
    touched = np.minimum(nrows, pages_of(base_size_bytes))
    return (T_IO_RAND * touched + CPU_ROW * nrows
            + beta(base_compression) * nrows * ncols_used)


def update_cost(index_size_bytes, index_nrows, rows_written, compression):
    alpha = 0.0 if compression is None else ALPHA[compression] * ALPHA_UNIT
    frac_written = np.where(
        np.asarray(index_nrows) <= 0, 1.0,
        np.minimum(rows_written / np.maximum(index_nrows, 1e-300), 1.0))
    io = T_IO_SEQ * pages_of(index_size_bytes * frac_written)
    cpu = (CPU_ROW + INDEX_MAINT_CPU) * rows_written
    return io + cpu + alpha * rows_written


class Optimizer:
    """What-if statement costs, memoized by (statement, table's indexes)."""

    def __init__(self, statements: Sequence, sizes: Sizes):
        self.statements = list(statements)
        self.sizes = sizes
        self._cache: Dict[Tuple, float] = {}

    def query_cost(self, q: QueryData, config: Config) -> float:
        table = self.sizes.tables[q.table]
        ncols_used = len(all_cols(q))
        clustered = clustered_of(config, q.table)
        base_size = self.sizes.size(clustered)
        best = scan_cost(base_size, table.nrows, ncols_used,
                         clustered.compression)
        filt = {c: (lo, hi) for c, lo, hi in q.filters}
        need = set(all_cols(q))
        for idx in for_table(config, q.table):
            if idx.clustered:
                continue
            nrows_idx = float(table.nrows)
            isize = self.sizes.size(idx)
            sel, matched = 1.0, False
            for c in idx.cols:
                if c not in filt:
                    break
                sel *= selectivity(table, c, *filt[c])
                matched = True
            sel = sel if matched else 1.0
            if need <= set(idx.cols):
                if sel < 1.0:
                    cost = seek_cost(isize, nrows_idx, sel, ncols_used,
                                     idx.compression)
                else:
                    cost = scan_cost(isize, nrows_idx, ncols_used,
                                     idx.compression)
            else:
                if sel >= 1.0:
                    continue
                cost = seek_cost(isize, nrows_idx, sel, len(idx.cols),
                                 idx.compression)
                cost += rid_lookup_cost(nrows_idx * sel, base_size,
                                        clustered.compression, ncols_used)
            best = min(best, cost)
        return best

    def insert_cost(self, s: InsertData, config: Config) -> float:
        total = 0.0
        for idx in for_table(config, s.table):
            total += update_cost(self.sizes.size(idx),
                                 float(self.sizes.tables[idx.table].nrows),
                                 s.nrows, idx.compression)
        return total

    def statement_cost(self, s, config: Config) -> float:
        relevant = for_table(config, s.table)
        key = (s.name, relevant)
        got = self._cache.get(key)
        if got is None:
            got = (self.query_cost(s, config) if isinstance(s, QueryData)
                   else self.insert_cost(s, config))
            self._cache[key] = got
        return got

    def workload_cost(self, config: Config) -> float:
        return sum(s.weight * self.statement_cost(s, config)
                   for s in self.statements)


def base_configuration(tables: Dict[str, RefTable]) -> Config:
    return frozenset(Index(t.name, tuple(c for c, _ in t.columns),
                           clustered=True) for t in tables.values())


def storage_used(config: Config, base: Config, sizes: Sizes) -> float:
    return (sum(sizes.size(i) for i in config)
            - sum(sizes.size(i) for i in base))


# --- candidates (§6.1) ------------------------------------------------------

def relevant_indexes(q: QueryData, table: RefTable) -> List[Index]:
    filters = sorted(q.filters, key=lambda p: selectivity(table, *p))
    fcols = [c for c, _, _ in filters]
    out: List[Index] = []
    seen = set()

    def add(cols, clustered=False):
        if not cols or (cols, clustered) in seen:
            return
        seen.add((cols, clustered))
        out.append(Index(q.table, cols, clustered=clustered))

    for c in fcols:
        add((c,))
    if len(fcols) > 1:
        add(tuple(fcols))
    covering = tuple(dict.fromkeys(fcols + list(q.cols_used)))
    add(covering)
    lead = tuple(dict.fromkeys(list(covering)
                               + [c for c, _ in table.columns]))
    add(lead, clustered=True)
    return out


def expand(indexes: Sequence[Index]) -> List[Index]:
    out: List[Index] = []
    for idx in indexes:
        out.append(idx)
        out.extend(idx.with_compression(m) for m in METHODS)
    return out


def merged_candidates(per_query: Dict[str, List[Index]]) -> List[Index]:
    flat: List[Index] = []
    seen = set()
    for cands in per_query.values():
        for idx in cands:
            if idx.clustered or idx.compression is not None:
                continue
            if idx not in seen:
                seen.add(idx)
                flat.append(idx)
    out: List[Index] = []
    oseen = set()
    for i, a in enumerate(flat):
        for b in flat[i + 1:]:
            if a.table != b.table or a.cols[0] != b.cols[0]:
                continue
            if set(a.cols) == set(b.cols):
                continue
            m = Index(a.table, tuple(dict.fromkeys(list(a.cols)
                                                   + list(b.cols))))
            if m not in oseen and m not in seen:
                oseen.add(m)
                out.append(m)
            if len(out) >= MAX_MERGES:
                return out
    return out


def skyline(cands: Sequence[Tuple[Index, float, float]]
            ) -> List[Tuple[Index, float, float]]:
    """(index, size, cost) Pareto frontier, then at most
    MAX_SKYLINE_POINTS representatives spread over size."""
    front = []
    for c in cands:
        dominated = any(
            o is not c and o[2] <= c[2] and o[1] <= c[1]
            and (o[2] < c[2] or o[1] < c[1]) for o in cands)
        if not dominated:
            front.append(c)
    front.sort(key=lambda c: (c[1], c[2]))
    if len(front) <= MAX_SKYLINE_POINTS:
        return front
    pts = sorted(front, key=lambda c: c[1])
    step = (len(pts) - 1) / (MAX_SKYLINE_POINTS - 1)
    picked = [pts[int(round(i * step))] for i in range(MAX_SKYLINE_POINTS)]
    return list({c[0]: c for c in picked}.values())


def _apply(config: Config, idx: Index) -> Config:
    if idx.clustered:
        old = clustered_of(config, idx.table)
        return (config - {old}) | {idx}
    return config | {idx}


def _present(config: Config, idx: Index) -> bool:
    return any(i.table == idx.table and i.cols == idx.cols
               and i.clustered == idx.clustered for i in config)


def _recover_oversized(config: Config, base: Config, pool: Sequence[Index],
                       sizes: Sizes, cost_fn, budget: float
                       ) -> Optional[Config]:
    """Figure 8: swap members for compressed variants until it fits."""
    best = None
    frontier = [config]
    seen = {config}
    for _ in range(4):
        nxt = []
        for cfg in frontier:
            for idx in sorted(cfg, key=Index.label):
                if idx.compression is not None:
                    continue
                for var in (p for p in pool
                            if p.table == idx.table and p.cols == idx.cols
                            and p.clustered == idx.clustered
                            and p.compression is not None):
                    cfg2 = (cfg - {idx}) | {var}
                    if cfg2 in seen:
                        continue
                    seen.add(cfg2)
                    if storage_used(cfg2, base, sizes) <= budget:
                        c = cost_fn(cfg2)
                        if best is None or c < best[0]:
                            best = (c, cfg2)
                    else:
                        nxt.append(cfg2)
        if best is not None or not nxt:
            break
        frontier = nxt
    return best[1] if best else None


def greedy(opt: Optimizer, sizes: Sizes, pool: Sequence[Index],
           base: Config, budget: float) -> Tuple[Config, float]:
    """Backtracking greedy enumeration (§6.2)."""
    config = base
    cost = opt.workload_cost(config)
    for _ in range(MAX_INDEXES):
        used = storage_used(config, base, sizes)
        best_feasible = best_any = None
        for idx in pool:
            if _present(config, idx):
                continue
            cfg2 = _apply(config, idx)
            used2 = storage_used(cfg2, base, sizes)
            benefit = cost - opt.workload_cost(cfg2)
            if benefit <= 1e-9:
                continue
            entry = (benefit, idx, cfg2)
            if used2 <= budget and (best_feasible is None
                                    or benefit > best_feasible[0]):
                best_feasible = entry
            if best_any is None or benefit > best_any[0]:
                best_any = entry
        chosen = None
        if best_any is not None and (best_feasible is None
                                     or best_any[1] != best_feasible[1]):
            recovered = _recover_oversized(best_any[2], base, pool, sizes,
                                           opt.workload_cost, budget)
            cand_cost = (opt.workload_cost(recovered)
                         if recovered is not None else float("inf"))
            feas_cost = (opt.workload_cost(best_feasible[2])
                         if best_feasible is not None else float("inf"))
            if recovered is not None and cand_cost < min(feas_cost, cost):
                chosen = recovered
            elif best_feasible is not None:
                chosen = best_feasible[2]
        elif best_feasible is not None:
            chosen = best_feasible[2]
        if chosen is None:
            break
        config = chosen
        cost = opt.workload_cost(config)
    return config, cost


@dataclasses.dataclass
class Universe:
    """Candidates of a workload: per query (expanded), merged (expanded),
    and the estimation targets in the advisor's order."""
    per_query: Dict[str, List[Index]]
    merged: List[Index]
    targets: List[Key]


def universe(statements: Sequence, tables: Dict[str, RefTable]) -> Universe:
    queries = [s for s in statements if isinstance(s, QueryData)]
    raw = {q.name: relevant_indexes(q, tables[q.table]) for q in queries}
    seen: Dict[Index, Index] = {}
    for cands in raw.values():
        for idx in cands:
            seen.setdefault(idx, idx)
    merged = merged_candidates(raw)
    for idx in merged:
        seen.setdefault(idx, idx)
    union = sorted(seen, key=lambda i: (i.table, i.cols, i.clustered))
    targets = list(dict.fromkeys(Key(i.table, i.cols, m)
                                 for i in union for m in METHODS))
    return Universe({n: expand(c) for n, c in raw.items()}, expand(merged),
                    targets)


def recommend(statements: Sequence, tables: Dict[str, RefTable],
              sizes: Sizes, budget: float) -> Tuple[Config, float]:
    """The reference recommendation over registered compressed sizes."""
    uni = universe(statements, tables)
    base = base_configuration(tables)
    opt = Optimizer(statements, sizes)
    pool: Dict[Index, Index] = {}
    for s in statements:
        if not isinstance(s, QueryData):
            continue
        costed = []
        for idx in uni.per_query[s.name]:
            if idx.clustered:
                old = clustered_of(base, idx.table)
                size = sizes.size(idx) - sizes.size(old)
                cost = opt.statement_cost(s, (base - {old}) | {idx})
            else:
                size = sizes.size(idx)
                cost = opt.statement_cost(s, base | {idx})
            costed.append((idx, size, cost))
        for idx, _, _ in skyline(costed):
            pool.setdefault(idx, idx)
    for idx in uni.merged:
        pool.setdefault(idx, idx)
    return greedy(opt, sizes, list(pool), base, budget)
