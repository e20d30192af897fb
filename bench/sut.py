"""The system under test, seen from the benchmark: the advisor's types,
built from the plain data of `bench.gen`.  The only module of the
benchmark's own that imports the program."""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Sequence

ROOT = Path(__file__).resolve().parents[1]


def add_program_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def schema(data):
    from repro.core import ColumnDef, ForeignKey, Schema, Table
    tables = {name: Table(name, [ColumnDef(c, w) for c, w in t.columns],
                          t.values)
              for name, t in data.tables.items()}
    return Schema(tables, [ForeignKey(*fk) for fk in data.fks])


def statement(s):
    from repro.core import BulkInsert, Predicate, Query
    from bench.gen import QueryData
    if isinstance(s, QueryData):
        return Query(s.name, s.table,
                     tuple(Predicate(c, lo, hi) for c, lo, hi in s.filters),
                     s.cols_used, weight=s.weight)
    return BulkInsert(s.name, s.table, s.nrows, weight=s.weight)


def workload(sch, stmts: Sequence):
    from repro.core import Workload
    return Workload(schema=sch, statements=[statement(s) for s in stmts])
