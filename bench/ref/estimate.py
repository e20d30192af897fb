"""Plain reference of the advisor's size estimation: tables, SampleCF over
the NS and LDICT codecs, and the §4.2 deductions; the plan they follow
comes from the reference planner (`bench.ref.plan`).

A frozen, standalone copy of the advisor's scalar numpy oracle (the
per-target `sample_cf` path and the `deduction` module), kept with the
benchmark so that no later change to the program can move it.  It imports
nothing of the program.  The arithmetic is the oracle's, operation for
operation, so a sound program matches it bit for bit.

`Reference(..., control=True)` is the comparison's control: every sample
value is rounded to bfloat16 before the codecs see it, the precision step
a later change could be tempted by.  It must come out as not correct.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

PAGE_BYTES = 8192
ROW_OVERHEAD = 4
PAGE_META = 16
ORDER_DEPENDENT = {"NS": False, "LDICT": True}
# Appendix-C style SampleCF bias fits (E[X] = 1 + bias * -ln f)
SAMPLECF_BIAS = {False: 0.0, True: 0.08}


def rows_per_page(row_width: int) -> int:
    return max(1, PAGE_BYTES // (row_width + ROW_OVERHEAD))


def uncompressed_payload_bytes(nrows: int, widths: Sequence[int]) -> int:
    return nrows * (int(sum(widths)) + ROW_OVERHEAD)


def _pack(stacked: List[np.ndarray], widths: Sequence[int]):
    """Columns packed into one int64 key when their widths fit 63 bits,
    else None.  Order and equality of the packed key are those of the
    column tuple, since every value fits its width."""
    if 8 * sum(widths) > 63:
        return None
    key = np.zeros(stacked[0].shape[0], dtype=np.int64)
    for v, w in zip(stacked, widths):
        key = (key << np.int64(8 * w)) | v
    return key


class RefTable:
    """Columns, widths and the optimizer statistics the deductions read."""

    def __init__(self, name: str, columns, values: Dict[str, np.ndarray]):
        self.name = name
        self.columns = tuple(columns)
        self.width = dict(self.columns)
        self.values = {c: np.asarray(values[c], dtype=np.int64)
                       for c, _ in self.columns}
        self.nrows = int(self.values[self.columns[0][0]].shape[0])
        self._ndv: Dict[Tuple[str, ...], int] = {}
        self._rf: Dict[Tuple, float] = {}
        self._minmax: Dict[str, Tuple[int, int]] = {}

    def ndv(self, cols: Sequence[str]) -> int:
        cols = tuple(cols)
        n = self._ndv.get(cols)
        if n is None:
            vals = [self.values[c] for c in cols]
            key = _pack(vals, [self.width[c] for c in cols])
            if key is not None:
                n = int(np.unique(key).size)
            else:
                n = int(np.unique(np.stack(vals, axis=1), axis=0).shape[0])
            self._ndv[cols] = n
        return n

    def minmax(self, col: str) -> Tuple[int, int]:
        got = self._minmax.get(col)
        if got is None:
            v = self.values[col]
            got = self._minmax[col] = (int(v.min()), int(v.max()))
        return got


# --- codecs (payload bytes of one column in index order) -------------------

def significant_bytes(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v, dtype=np.uint64)
    out = np.ones(v.shape, dtype=np.int64)
    for k in range(1, 8):
        out += (v >= np.uint64(1) << np.uint64(8 * k)).astype(np.int64)
    return out


def ns_bytes(col: np.ndarray, width: int, rpp: int) -> int:
    sig = np.minimum(significant_bytes(col), width)
    half_bytes = np.minimum(2 * sig + 1, 2 * width)
    return int((int(np.sum(half_bytes)) + 1) // 2)


def ldict_bytes(col: np.ndarray, width: int, rpp: int) -> int:
    n = col.shape[0]
    npages = -(-n // rpp)
    pad = npages * rpp - n
    if pad:
        col = np.concatenate([col, np.repeat(col[-1], pad)])
    pages = col.reshape(npages, rpp)
    srt = np.sort(pages, axis=1)
    ndv_p = 1 + np.count_nonzero(np.diff(srt, axis=1), axis=1)
    ptr = np.where(ndv_p <= 256, 1, np.where(ndv_p <= 65536, 2, 3))
    rows_in_page = np.full(npages, rpp, dtype=np.int64)
    if n % rpp:
        rows_in_page[-1] = n % rpp
    per_page = ndv_p * width + rows_in_page * ptr + PAGE_META
    cap = rows_in_page * width
    return int(np.sum(np.minimum(per_page, cap + PAGE_META)))


CODECS = {"NS": ns_bytes, "LDICT": ldict_bytes}


def compressed_payload_bytes(method: str, data: np.ndarray,
                             widths: Sequence[int]) -> int:
    rpp = rows_per_page(int(sum(widths)))
    total = data.shape[0] * ROW_OVERHEAD
    for j, w in enumerate(widths):
        total += CODECS[method](data[:, j], int(w), rpp)
    return int(total)


def bfloat16_round(v: np.ndarray) -> np.ndarray:
    """Non-negative integers rounded to bfloat16 (nearest, ties to even)."""
    u = v.astype(np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    # a value that rounds up to 2**63 keeps the largest float64 below it
    top = float(np.iinfo(np.int64).max - 1023)
    return np.minimum(u.view(np.float32).astype(np.float64),
                      top).astype(np.int64)


# --- SampleCF ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Key:
    table: str
    cols: Tuple[str, ...]
    method: str


class Reference:
    """SampleCF estimates and §4.2 deductions over one database."""

    def __init__(self, tables: Dict[str, RefTable], sample_seed: int = 0,
                 control: bool = False):
        self.tables = tables
        self.seed = int(sample_seed)
        self.control = control
        self._samples: Dict[Tuple[str, float], Dict[str, np.ndarray]] = {}
        self._sampled: Dict[Tuple[Key, float], float] = {}
        self._plans: Dict[Tuple, "PlainPlan"] = {}

    def plan(self, targets: Sequence[Key], e: float, q: float
             ) -> "PlainPlan":
        """The reference planner's plan for these targets (`bench.ref.plan`),
        made once per target list."""
        key = (tuple(targets), e, q)
        got = self._plans.get(key)
        if got is None:
            from bench.ref.plan import Planner
            got = self._plans[key] = Planner(self.tables).plan(targets, e, q)
        return got

    def sample(self, table: str, f: float) -> Dict[str, np.ndarray]:
        """A uniform sample of fraction f, one stream per (table, f)."""
        key = (table, round(f, 6))
        got = self._samples.get(key)
        if got is None:
            t = self.tables[table]
            n = min(max(2, int(round(t.nrows * f))), t.nrows)
            rng = np.random.default_rng(
                (self.seed, zlib.crc32(table.encode("utf-8")),
                 int(round(round(f, 6) * 1e6))))
            rows = np.sort(rng.choice(t.nrows, size=n, replace=False))
            got = {c: t.values[c][rows] for c, _ in t.columns}
            if self.control:
                got = {c: bfloat16_round(v) for c, v in got.items()}
            self._samples[key] = got
        return got

    def sample_cf(self, k: Key, f: float) -> float:
        """Estimated compressed bytes of index k from its sample."""
        got = self._sampled.get((k, f))
        if got is not None:
            return got
        table = self.tables[k.table]
        sample = self.sample(k.table, f)
        widths = [table.width[c] for c in k.cols]
        keys = [sample[c] for c in reversed(k.cols)]
        order = np.lexsort(keys)
        data = np.stack([sample[c][order] for c in k.cols], axis=1)
        s = uncompressed_payload_bytes(data.shape[0], widths)
        full_bytes = uncompressed_payload_bytes(table.nrows, widths)
        if data.shape[0] == 0 or s == 0:
            cf = 1.0
        else:
            sc = compressed_payload_bytes(k.method, data, widths)
            cf = sc / s
            lf = -math.log(max(min(f, 1.0), 1e-9))
            bias = 1.0 + SAMPLECF_BIAS[ORDER_DEPENDENT[k.method]] * lf
            cf = min(cf / bias, 1.0)
        got = self._sampled[(k, f)] = cf * full_bytes
        return got

    # --- deductions (§4.2) --------------------------------------------------
    def _usize(self, table: RefTable, cols: Sequence[str]) -> float:
        return float(uncompressed_payload_bytes(
            table.nrows, [table.width[c] for c in cols]))

    def _replaced_fraction(self, table: RefTable,
                           index_cols: Tuple[str, ...],
                           cols: Sequence[str]) -> List[float]:
        """F(I_X, Y) = (T - DV) / T for each Y in cols, X = index_cols."""
        missing = [c for c in cols if (index_cols, c) not in table._rf]
        if missing:
            t = rows_per_page(sum(table.width[c] for c in index_cols))
            tf = float(t)
            L = np.array([table.nrows / max(table.ndv(
                index_cols[:index_cols.index(c) + 1]), 1) for c in missing])
            long_runs = L > 1.0
            dv = np.minimum(tf, np.ceil(t / np.where(long_runs, L, 1.0)))
            for i in np.nonzero(~long_runs)[0].tolist():
                y = table.ndv([missing[i]])
                dv[i] = y - y * (1.0 - 1.0 / max(y, 1)) ** t
            frac = np.maximum((t - dv) / t, 0.0)
            for c, v in zip(missing, frac.tolist()):
                table._rf[(index_cols, c)] = v
        return [table._rf[(index_cols, c)] for c in cols]

    def deduce(self, k: Key, parts: Sequence[Tuple[Tuple[str, ...], float]]
               ) -> float:
        """ColExt: the target's size from a partition of its columns."""
        table = self.tables[k.table]
        s_target = self._usize(table, k.cols)
        r_total = 0.0
        if not ORDER_DEPENDENT[k.method]:
            for part_cols, csize in parts:
                r_total += self._usize(table, part_cols) - csize
            return max(s_target - r_total, 0.0)
        for part_cols, csize in parts:
            r_part = self._usize(table, part_cols) - csize
            if r_part <= 0:
                continue
            widths = {c: table.width[c] for c in part_cols}
            wsum = sum(widths.values())
            f_parts = self._replaced_fraction(table, tuple(part_cols),
                                              part_cols)
            f_targets = self._replaced_fraction(table, tuple(k.cols),
                                                part_cols)
            for i, col in enumerate(part_cols):
                r_col = r_part * widths[col] / max(wsum, 1)
                if f_parts[i] <= 1e-9:
                    continue
                r_total += r_col * min(f_targets[i] / f_parts[i], 1.5)
        return max(s_target - r_total, 0.0)

    def resolve(self, plan: "PlainPlan") -> Dict[Key, float]:
        """Every node's size under the plan: SampleCF where it samples,
        the deduction it names where it deduces."""
        out: Dict[Key, float] = {}

        def size(k: Key) -> float:
            if k in out:
                return out[k]
            kind, children = plan.nodes[k]
            if kind == "SAMPLED":
                v = self.sample_cf(k, plan.f)
            elif kind == "colset":
                v = size(children[0])
            elif kind == "colext":
                v = self.deduce(k, [(c.cols, size(c)) for c in children])
            else:
                raise ValueError(f"plan node {k} has state {kind!r}")
            out[k] = v
            return v

        for k in plan.targets:
            size(k)
        for k in plan.nodes:
            size(k)
        return out


@dataclasses.dataclass
class PlainPlan:
    """An estimation plan as data: the sampling fraction, the targets,
    and per node ("SAMPLED", ()) or (deduction kind, child keys)."""
    f: float
    targets: Tuple[Key, ...]
    nodes: Dict[Key, Tuple[str, Tuple[Key, ...]]]


def tables_from(schema_data) -> Dict[str, RefTable]:
    return {name: RefTable(name, t.columns, t.values)
            for name, t in schema_data.tables.items()}
