import numpy as np
import pytest

from presto_tpu.connectors.spi import TableHandle
from presto_tpu.connectors.tpch import (
    TpchConnector, _lines_per_order, tpch_schema, TABLES,
)


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.001)  # tiny: 1500 orders, ~6000 lineitems


def _scan(conn, table, columns, desired_splits=1, rows_per_batch=1 << 17):
    th = TableHandle("tpch", "tiny", table)
    out = []
    for split in conn.split_manager.splits(th, desired_splits):
        src = conn.page_source(split, columns, rows_per_batch=rows_per_batch)
        out.extend(b.to_pylist() for b in src.batches())
    return [r for rows in out for r in rows]


def test_all_tables_scan(conn):
    for t in TABLES:
        cols = tpch_schema(t).names[:3]
        rows = _scan(conn, t, cols)
        assert len(rows) > 0, t


def test_row_counts(conn):
    assert len(_scan(conn, "orders", ["o_orderkey"])) == 1500
    assert len(_scan(conn, "customer", ["c_custkey"])) == 150
    assert len(_scan(conn, "nation", ["n_nationkey"])) == 25
    assert len(_scan(conn, "region", ["r_regionkey"])) == 5
    n_li = len(_scan(conn, "lineitem", ["l_orderkey"]))
    assert 4000 < n_li < 8000  # ~4 lines/order


def test_determinism_across_splits(conn):
    one = _scan(conn, "orders", ["o_orderkey", "o_custkey", "o_orderdate"], 1)
    four = _scan(conn, "orders", ["o_orderkey", "o_custkey", "o_orderdate"], 4)
    assert sorted(one) == sorted(four)


def test_lineitem_split_determinism(conn):
    cols = ["l_orderkey", "l_linenumber", "l_extendedprice", "l_shipdate"]
    one = _scan(conn, "lineitem", cols, 1)
    three = _scan(conn, "lineitem", cols, 3, rows_per_batch=512)
    assert sorted(one) == sorted(three)


def test_referential_integrity(conn):
    custkeys = {r[0] for r in _scan(conn, "customer", ["c_custkey"])}
    orders = _scan(conn, "orders", ["o_custkey"])
    assert all(r[0] in custkeys for r in orders)

    partkeys = {r[0] for r in _scan(conn, "part", ["p_partkey"])}
    suppkeys = {r[0] for r in _scan(conn, "supplier", ["s_suppkey"])}
    li = _scan(conn, "lineitem", ["l_partkey", "l_suppkey"])
    assert all(r[0] in partkeys for r in li)
    assert all(r[1] in suppkeys for r in li)

    ps = _scan(conn, "partsupp", ["ps_partkey", "ps_suppkey"])
    assert all(r[0] in partkeys and r[1] in suppkeys for r in ps)


def test_extendedprice_consistency(conn):
    # l_extendedprice == l_quantity * p_retailprice(l_partkey)
    prices = dict(
        (r[0], r[1]) for r in _scan(conn, "part", ["p_partkey", "p_retailprice"]))
    li = _scan(conn, "lineitem", ["l_partkey", "l_quantity", "l_extendedprice"])
    for pk, qty, ext in li[:500]:
        assert abs(ext - qty * prices[pk]) < 1e-6


def test_date_ranges_and_enums(conn):
    import datetime

    rows = _scan(conn, "lineitem", ["l_shipdate", "l_returnflag", "l_linestatus",
                                    "l_shipmode", "l_discount"])
    for d, rf, ls, mode, disc in rows[:1000]:
        assert datetime.date(1992, 1, 2) <= d <= datetime.date(1999, 1, 1)
        assert rf in ("A", "N", "R")
        assert ls in ("O", "F")
        assert 0.0 <= disc <= 0.10
    # Q6-ish selectivity sanity: discount in [0.05,0.07] ~ 3/11 of rows
    frac = sum(1 for r in rows if 0.05 <= r[4] <= 0.07) / len(rows)
    assert 0.15 < frac < 0.40


def test_stable_dictionaries_across_batches(conn):
    th = TableHandle("tpch", "tiny", "lineitem")
    split = conn.split_manager.splits(th, 1)[0]
    src = conn.page_source(split, ["l_returnflag", "l_shipmode"],
                           rows_per_batch=512)
    dicts = set()
    for b in src.batches():
        dicts.add((b.column("l_returnflag").dictionary,
                   b.column("l_shipmode").dictionary))
    assert len(dicts) == 1  # stable vocab -> one compiled kernel


def test_stats(conn):
    th = TableHandle("tpch", "tiny", "orders")
    st = conn.metadata.table_stats(th)
    assert st.row_count == 1500
    assert st.columns["o_orderkey"].max_value == 1500


@pytest.mark.parametrize("tables", [("lineitem",), ("orders", "customer")])
def test_a_catalog_of_some_tables_holds_those_alone(tables):
    conn = TpchConnector(sf=0.001, tables=list(tables))
    assert conn.metadata.list_tables() == list(tables)
    for t in TABLES:
        th = TableHandle("tpch", "tiny", t)
        if t in tables:
            assert conn.metadata.table_schema(th).names == tpch_schema(t).names
            assert len(_scan(conn, t, tpch_schema(t).names[:1])) > 0
        else:
            with pytest.raises(KeyError, match=t):
                conn.metadata.table_schema(th)


def test_a_catalog_of_an_unknown_table_is_refused():
    with pytest.raises(ValueError, match="nope"):
        TpchConnector(sf=0.001, tables=["lineitem", "nope"])
    assert TpchConnector(sf=0.001).metadata.list_tables() == list(TABLES)
