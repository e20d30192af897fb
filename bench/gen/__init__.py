"""Data generators, one module per generator named by a configuration's
`generator` key (`bench/gen/<generator>.py`).  Each exposes
`make(config) -> SchemaData` and `workload(schema, insert_weight)`.

The plain data they make, shared by the system under test (through
`bench.sut`) and the reference (`bench.ref`), so that neither sees the
other's types.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import numpy as np


@dataclasses.dataclass
class TableData:
    name: str
    columns: Tuple[Tuple[str, int], ...]      # (column, byte width)
    values: Dict[str, np.ndarray]              # column -> int64 values

    @property
    def nrows(self) -> int:
        return int(self.values[self.columns[0][0]].shape[0])

    def minmax(self, col: str) -> Tuple[int, int]:
        v = self.values[col]
        return int(v.min()), int(v.max())


@dataclasses.dataclass
class SchemaData:
    tables: Dict[str, TableData]
    fks: Tuple[Tuple[str, str, str, str], ...]   # (fact, fk col, dim, key)


@dataclasses.dataclass(frozen=True)
class QueryData:
    name: str
    table: str
    filters: Tuple[Tuple[str, int, int], ...]    # (col, lo, hi), inclusive
    cols_used: Tuple[str, ...]
    weight: float = 1.0


@dataclasses.dataclass(frozen=True)
class InsertData:
    name: str
    table: str
    nrows: int
    weight: float = 1.0


StatementData = Union[QueryData, InsertData]
