"""The benchmark's copied generators make what the program's own make,
and a configuration's database is TPC-H's, table for table and byte for
byte."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import sut
from bench.gen import tpch
from bench.ref.estimate import significant_bytes

sut.add_program_path()

from repro.core import make_tpch_like, make_tpch_workload  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# TPC-H v3, clause 1.4: each table's columns and their declared bytes
# (INTEGER, DATE and DECIMAL as 4, CHAR(n) and VARCHAR(n) as n), less the
# program generator's integer coding of the columns it already holds
TPCH_COLUMNS = {"lineitem": 16, "orders": 9, "part": 9, "partsupp": 5,
                "supplier": 7, "customer": 8, "nation": 4, "region": 3}

SCALE = 0.5


@pytest.fixture(scope="module", params=[(0.0, 0), (1.0, 0), (0.0, 5)],
                ids=["z0_seed0", "z1_seed0", "z0_seed5"])
def both(request):
    z, seed = request.param
    return (tpch.make_tpch_like(scale=SCALE, z=z, seed=seed),
            make_tpch_like(scale=SCALE, z=z, seed=seed))


def test_tables_are_the_programs(both):
    data, program = both
    assert list(data.tables) == list(program.tables)
    for name, t in data.tables.items():
        pt = program.tables[name]
        assert t.columns == tuple((c.name, c.width) for c in pt.columns)
        for c, _ in t.columns:
            np.testing.assert_array_equal(t.values[c], pt.values[c])
    assert [tuple(fk) for fk in data.fks] == [
        (fk.fact_table, fk.fk_col, fk.dim_table, fk.dim_key)
        for fk in program.foreign_keys]


@pytest.mark.parametrize("insert_weight", [0.1, 20.0])
def test_tpch_workload_is_the_programs(both, insert_weight):
    data, program = both
    got = sut.workload(program, tpch.make_tpch_workload(
        data, insert_weight=insert_weight)).statements
    assert got == make_tpch_workload(program,
                                     insert_weight=insert_weight).statements


@pytest.fixture(scope="module", params=["tpch_sf1", "tpch_sf1_z1"])
def config(request):
    c = json.loads((ROOT / f"bench/configs/{request.param}.json").read_text())
    return dict(c, scale=SCALE)


def _logical(columns):
    """Column name -> declared bytes, payload pieces folded back."""
    out = {}
    for c, w in columns:
        base = c.rsplit("_", 1)[0] if c.rsplit("_", 1)[-1].isdigit() else c
        out[base] = out.get(base, 0) + w
    return out


def test_a_configuration_has_every_tpch_table_and_column(config):
    data = tpch.make(config)
    assert set(data.tables) == set(TPCH_COLUMNS) == set(config["rows"])
    for name, t in data.tables.items():
        assert len(_logical(t.columns)) == TPCH_COLUMNS[name], name
        assert all(1 <= w <= 8 for _, w in t.columns)
    rows = {n: t.nrows for n, t in data.tables.items()}
    assert rows["supplier"] == tpch.supplier_rows(config) == 100 * SCALE
    assert rows["partsupp"] == 4 * rows["part"]
    assert (rows["nation"], rows["region"]) == (25, 5)
    widths = _logical(data.tables["lineitem"].columns)
    assert (widths["l_comment"], widths["l_shipinstruct"]) == (44, 25)


def test_a_configuration_keeps_the_programs_columns(config):
    """The added columns come from a stream of their own: the program
    generator's columns are as it makes them, with TPC-H's supplier
    count."""
    data = tpch.make(config)
    program = make_tpch_like(scale=SCALE, z=config["z"], seed=0)
    plain = tpch.make_tpch_like(scale=SCALE, z=config["z"], seed=0,
                                suppliers=tpch.supplier_rows(config))
    for name, pt in program.tables.items():
        for c in pt.columns:
            np.testing.assert_array_equal(data.tables[name].values[c.name],
                                          plain.tables[name].values[c.name])
    np.testing.assert_array_equal(data.tables["orders"].values["o_custkey"],
                                  program.tables["orders"].values["o_custkey"])
    assert sut.schema(data).tables["lineitem"].nrows == SCALE * 60_000


@pytest.mark.parametrize("width,lengths", [(44, [10, 43]), (25, [4, 17]),
                                           (15, [15, 15])])
def test_text_fills_as_many_bytes_as_its_length(width, lengths):
    rng = np.random.default_rng(3)
    n = rng.integers(lengths[0], lengths[1] + 1, 5000)
    pieces = tpch._pieces(rng, n, width)
    assert len(pieces) == -(-width // 8)
    for j, v in enumerate(pieces):
        want = np.clip(n - 8 * j, 0, min(8, width - 8 * j))
        got = np.where(v == 0, 0, significant_bytes(v))
        np.testing.assert_array_equal(got, want)
        assert v.min() >= 0
