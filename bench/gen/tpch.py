"""TPC-H data and the App. D.2 workload, as plain numpy data.

`make(config)` builds a configuration's database: the program's
TPC-H-shaped tables (`make_tpch_like`, a frozen copy of the generator
the advisor ships with, draw for draw), with the supplier count the
configuration states, then the columns and tables the configuration adds
so that every table has TPC-H's columns at their declared widths
(`add_columns`).  `workload` is the App. D.2 workload
(`make_tpch_workload`, a frozen copy too).  The copies live here so that
the yardstick cannot move when the program's own generators change;
`bench/tests/test_bench_gen.py` pins them equal to the program's.

Columns the advisor holds are integer-coded and at most 8 bytes wide, so
a text column of n bytes is carried as ceil(n / 8) payload columns of 8
bytes (the last holds the rest).  A value of length L fills its first L
bytes with non-zero bytes and leaves the rest zero, as a padded CHAR or
VARCHAR field does: the uncompressed row has the declared width, and null
suppression finds what a row format would.

The output is plain data (`SchemaData`, `QueryData`, `InsertData`).  The
system under test gets it through `bench.sut`, the reference through
`bench.ref`, so neither sees the other's types.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from bench.gen import (InsertData, QueryData, SchemaData,  # noqa: F401
                       StatementData, TableData)


def _zipf_choice(rng: np.random.Generator, n_distinct: int, size: int,
                 z: float) -> np.ndarray:
    if z <= 0:
        return rng.integers(0, n_distinct, size=size)
    ranks = np.arange(1, n_distinct + 1, dtype=np.float64)
    p = ranks ** (-z)
    p /= p.sum()
    return rng.choice(n_distinct, size=size, p=p)


def _table(name, columns, values) -> TableData:
    return TableData(name, tuple(columns),
                     {c: np.asarray(values[c], dtype=np.int64)
                      for c, _ in columns})


def make_tpch_like(scale: float = 1.0, z: float = 0.0, seed: int = 0,
                   suppliers: Optional[int] = None) -> SchemaData:
    """TPC-H-shaped schema; `scale` 1 is 60k lineitem rows, 100 is SF1.

    `suppliers` None keeps the program's supplier count (lineitem / 150);
    a number sets it, and so the range of `l_suppkey`."""
    rng = np.random.default_rng(seed)
    n_li = max(int(60_000 * scale), 1000)
    n_ord = max(n_li // 4, 100)
    n_part = max(n_li // 30, 50)
    n_supp = max(n_li // 150, 10) if suppliers is None else suppliers
    n_cust = max(n_ord // 10, 20)

    date_lo, n_dates = 728_000, 2_400  # ~6.5 years of day numbers

    orders = _table("orders", [
        ("o_orderkey", 4), ("o_custkey", 4), ("o_orderstatus", 1),
        ("o_totalprice", 4), ("o_orderdate", 4), ("o_orderpriority", 1),
        ("o_clerk", 2)], {
        "o_orderkey": np.arange(n_ord),
        "o_custkey": _zipf_choice(rng, n_cust, n_ord, z),
        "o_orderstatus": _zipf_choice(rng, 3, n_ord, z),
        "o_totalprice": rng.integers(1_000, 500_000, n_ord),
        "o_orderdate": date_lo + _zipf_choice(rng, n_dates, n_ord, z),
        "o_orderpriority": _zipf_choice(rng, 5, n_ord, z),
        "o_clerk": _zipf_choice(rng, 1000, n_ord, z),
    })

    li_orderkey = rng.integers(0, n_ord, n_li)
    li_shipdate = (orders.values["o_orderdate"][li_orderkey]
                   + rng.integers(1, 120, n_li))
    lineitem = _table("lineitem", [
        ("l_orderkey", 4), ("l_partkey", 4), ("l_suppkey", 4),
        ("l_quantity", 1), ("l_extendedprice", 4), ("l_discount", 1),
        ("l_tax", 1), ("l_returnflag", 1), ("l_linestatus", 1),
        ("l_shipdate", 4), ("l_shipmode", 1)], {
        "l_orderkey": li_orderkey,
        "l_partkey": _zipf_choice(rng, n_part, n_li, z),
        "l_suppkey": _zipf_choice(rng, n_supp, n_li, z),
        "l_quantity": 1 + _zipf_choice(rng, 50, n_li, z),
        "l_extendedprice": rng.integers(100, 100_000, n_li),
        "l_discount": _zipf_choice(rng, 11, n_li, z),
        "l_tax": _zipf_choice(rng, 9, n_li, z),
        "l_returnflag": _zipf_choice(rng, 3, n_li, z),
        "l_linestatus": _zipf_choice(rng, 2, n_li, z),
        "l_shipdate": li_shipdate,
        "l_shipmode": _zipf_choice(rng, 7, n_li, z),
    })

    part = _table("part", [
        ("p_partkey", 4), ("p_brand", 1), ("p_type", 1), ("p_size", 1),
        ("p_container", 1), ("p_retailprice", 4)], {
        "p_partkey": np.arange(n_part),
        "p_brand": _zipf_choice(rng, 25, n_part, z),
        "p_type": _zipf_choice(rng, 150, n_part, z) % 256,
        "p_size": 1 + _zipf_choice(rng, 50, n_part, z),
        "p_container": _zipf_choice(rng, 40, n_part, z),
        "p_retailprice": rng.integers(900, 2_000, n_part),
    })

    supplier = _table("supplier", [
        ("s_suppkey", 4), ("s_nationkey", 1), ("s_acctbal", 4)], {
        "s_suppkey": np.arange(n_supp),
        "s_nationkey": _zipf_choice(rng, 25, n_supp, z),
        "s_acctbal": rng.integers(0, 100_000, n_supp),
    })

    customer = _table("customer", [
        ("c_custkey", 4), ("c_nationkey", 1), ("c_mktsegment", 1),
        ("c_acctbal", 4)], {
        "c_custkey": np.arange(n_cust),
        "c_nationkey": _zipf_choice(rng, 25, n_cust, z),
        "c_mktsegment": _zipf_choice(rng, 5, n_cust, z),
        "c_acctbal": rng.integers(0, 100_000, n_cust),
    })

    fks = (("lineitem", "l_orderkey", "orders", "o_orderkey"),
           ("lineitem", "l_partkey", "part", "p_partkey"),
           ("lineitem", "l_suppkey", "supplier", "s_suppkey"),
           ("orders", "o_custkey", "customer", "c_custkey"))
    return SchemaData({t.name: t for t in
                       (lineitem, orders, part, supplier, customer)}, fks)


def make_tpch_workload(schema: SchemaData, insert_weight: float = 0.1,
                       query_weight: float = 1.0) -> List[StatementData]:
    """20 analytic queries + 2 bulk loads of 2% each (paper App. D.2).

    insert_weight 0.1 is SELECT-intensive, 20 INSERT-intensive."""
    li = schema.tables["lineitem"]
    od = schema.tables["orders"]
    dlo, dhi = li.minmax("l_shipdate")
    olo, ohi = od.minmax("o_orderdate")
    span = dhi - dlo
    ospan = ohi - olo

    def drange(frac_lo: float, frac_hi: float) -> Tuple[int, int]:
        return (int(dlo + span * frac_lo), int(dlo + span * frac_hi))

    def orange(fl, fh):
        return (int(olo + ospan * fl), int(olo + ospan * fh))

    qs: List[StatementData] = []

    def q(name, table, filters, cols):
        qs.append(QueryData(name, table, tuple(filters), tuple(cols),
                            weight=query_weight))

    a, b = drange(0.0, 0.9)
    q("q01", "lineitem", [("l_shipdate", a, b)],
      ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
       "l_discount", "l_tax"])
    a, b = drange(0.3, 0.45)
    q("q06", "lineitem", [("l_shipdate", a, b), ("l_discount", 5, 7),
                          ("l_quantity", 1, 24)],
      ["l_extendedprice", "l_discount"])
    a, b = drange(0.5, 0.65)
    q("q12", "lineitem", [("l_shipdate", a, b), ("l_shipmode", 2, 3)],
      ["l_orderkey", "l_shipmode"])
    a, b = drange(0.70, 0.72)
    q("q03", "lineitem", [("l_shipdate", a, b)],
      ["l_orderkey", "l_extendedprice", "l_discount"])
    a, b = drange(0.10, 0.13)
    q("q04", "lineitem", [("l_shipdate", a, b), ("l_returnflag", 1, 1)],
      ["l_extendedprice", "l_suppkey"])
    q("q05", "lineitem",
      [("l_suppkey", 0, max(2, li.minmax("l_suppkey")[1] // 20))],
      ["l_extendedprice", "l_discount", "l_shipdate"])
    q("q07", "lineitem", [("l_returnflag", 2, 2)],
      ["l_extendedprice", "l_quantity"])
    q("q08", "lineitem", [("l_shipmode", 5, 6)],
      ["l_extendedprice", "l_shipdate"])
    a, b = drange(0.2, 0.8)
    q("q09", "lineitem", [("l_shipdate", a, b), ("l_tax", 0, 2)],
      ["l_partkey", "l_extendedprice"])
    q("q10", "lineitem", [("l_quantity", 40, 50)],
      ["l_extendedprice", "l_discount", "l_partkey"])
    a, b = drange(0.55, 0.60)
    q("q11", "lineitem", [("l_shipdate", a, b)],
      ["l_suppkey", "l_quantity", "l_extendedprice"])
    q("q14", "lineitem",
      [("l_partkey", 0, max(2, li.minmax("l_partkey")[1] // 10))],
      ["l_extendedprice", "l_discount", "l_shipdate"])

    a, b = orange(0.4, 0.55)
    q("q21", "orders", [("o_orderdate", a, b)],
      ["o_totalprice", "o_orderpriority"])
    a, b = orange(0.8, 1.0)
    q("q22", "orders", [("o_orderdate", a, b), ("o_orderstatus", 0, 0)],
      ["o_totalprice", "o_custkey"])
    q("q23", "orders", [("o_orderpriority", 0, 1)],
      ["o_totalprice", "o_orderdate"])
    a, b = orange(0.1, 0.12)
    q("q24", "orders", [("o_orderdate", a, b)],
      ["o_custkey", "o_totalprice", "o_clerk"])
    q("q25", "orders",
      [("o_custkey", 0, max(2, od.minmax("o_custkey")[1] // 15))],
      ["o_totalprice", "o_orderdate"])
    q("q26", "customer", [("c_mktsegment", 1, 1)],
      ["c_custkey", "c_acctbal"])
    q("q27", "part", [("p_brand", 3, 4), ("p_size", 10, 20)],
      ["p_partkey", "p_retailprice"])
    q("q28", "part", [("p_container", 7, 9)],
      ["p_retailprice", "p_size"])

    qs.append(InsertData("load_lineitem", "lineitem",
                         max(li.nrows // 50, 100), weight=insert_weight))
    qs.append(InsertData("load_orders", "orders",
                         max(od.nrows // 50, 50), weight=insert_weight))
    return qs


# --- the configuration's database ------------------------------------------

def _pieces(rng: np.random.Generator, lengths: np.ndarray,
            width: int) -> List[np.ndarray]:
    """Text of the given byte lengths in a field of `width` bytes, as
    8-byte payload columns: piece j holds bytes 8j .. 8j+7 of the field,
    its significant bytes those the text reaches."""
    out = []
    for off in range(0, width, 8):
        k = np.clip(lengths - off, 0, min(8, width - off)).astype(np.int64)
        raw = rng.integers(0, np.iinfo(np.int64).max, lengths.shape[0],
                           dtype=np.int64)
        mask = np.where(k >= 8, np.iinfo(np.int64).max,
                        (np.int64(1) << (8 * np.minimum(k, 7))) - 1)
        lead = np.where(k > 0, np.int64(1) << (8 * np.maximum(k - 1, 0)), 0)
        out.append((raw & mask) | lead)
    return out


def _column(rng: np.random.Generator, spec: dict, n: int, z: float
            ) -> List[Tuple[str, int, np.ndarray]]:
    """One column spec of the configuration as (name, width, values)."""
    name, width = spec["name"], int(spec["width"])
    if "seq" in spec:
        return [(name, width, np.arange(n, dtype=np.int64) // spec["seq"])]
    if "int" in spec:
        lo, hi = spec["int"]
        return [(name, width, lo + _zipf_choice(rng, hi - lo + 1, n, z))]
    lo, hi = spec["text"]
    if "distinct" in spec:
        d = int(spec["distinct"])
        vocab = _pieces(rng, rng.integers(lo, hi + 1, d), width)
        pick = _zipf_choice(rng, d, n, z)
        pieces = [v[pick] for v in vocab]
    else:
        pieces = _pieces(rng, rng.integers(lo, hi + 1, n), width)
    if len(pieces) == 1:
        return [(name, width, pieces[0])]
    return [(f"{name}_{j}", min(8, width - 8 * j), v)
            for j, v in enumerate(pieces)]


def add_columns(schema: SchemaData, config: dict) -> SchemaData:
    """The columns (`columns`) and tables (`tables`) the configuration
    adds, drawn from a stream of the data seed of their own, so that the
    program's columns stay as its generator makes them."""
    rng = np.random.default_rng([config["data_seed"], 1])
    z = config["z"]
    tables = dict(schema.tables)
    for tname, specs in config.get("columns", {}).items():
        t = tables[tname]
        cols, vals = list(t.columns), dict(t.values)
        for spec in specs:
            for c, w, v in _column(rng, spec, t.nrows, z):
                cols.append((c, w))
                vals[c] = v
        tables[tname] = TableData(tname, tuple(cols), vals)
    for tname, spec in config.get("tables", {}).items():
        n = spec["rows"]
        if isinstance(n, list):                  # [table, rows per row]
            n = tables[n[0]].nrows * n[1]
        cols, vals = [], {}
        for cspec in spec["columns"]:
            for c, w, v in _column(rng, cspec, n, z):
                cols.append((c, w))
                vals[c] = v
        tables[tname] = TableData(tname, tuple(cols), vals)
    return SchemaData(tables, schema.fks)


def supplier_rows(config: dict) -> int:
    """TPC-H's supplier count, SF x 10,000 (`scale` 100 is SF 1)."""
    return max(int(config["supplier_rows_per_scale"] * config["scale"]), 10)


def make(config: dict) -> SchemaData:
    """The configuration's database."""
    base = make_tpch_like(scale=config["scale"], z=config["z"],
                          seed=config["data_seed"],
                          suppliers=supplier_rows(config))
    return add_columns(base, config)


def workload(schema: SchemaData, insert_weight: float
             ) -> List[StatementData]:
    """The App. D.2 workload at this insert weight."""
    return make_tpch_workload(schema, insert_weight=insert_weight)
