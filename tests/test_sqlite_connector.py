"""SQLite connector: a real external store behind the SPI.

Mirrors the reference's JDBC-connector test shape (reference
presto-base-jdbc + presto-mysql tests run the shared suites against a
real foreign database): CTAS engine data INTO sqlite, read it back
through the engine, check filter pushdown reaches sqlite's SQL, and
verify joins across catalogs work.
"""
import os

import pytest

from presto_tpu.connectors.spi import CatalogManager, TableHandle
from presto_tpu.connectors.sqlite import SqliteConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.runner import LocalRunner


@pytest.fixture(scope="module")
def runner(tmp_path_factory):
    db = str(tmp_path_factory.mktemp("sqlite") / "store.db")
    cat = CatalogManager()
    cat.register("tpch", TpchConnector(sf=0.01))
    cat.register("sq", SqliteConnector(db))
    r = LocalRunner(catalogs=cat, catalog="tpch")
    # CTAS a TPC-H subset INTO sqlite through the engine's write path
    r.execute("create table sq.default.nation2 as select * from nation")
    r.execute("""create table sq.default.orders2 as
                 select o_orderkey, o_custkey, o_totalprice, o_orderdate
                 from orders where o_orderkey < 1000""")
    return r


def test_metadata_discovery(runner):
    conn = runner.session.catalogs.get("sq")
    tables = conn.metadata.list_tables()
    assert "nation2" in tables and "orders2" in tables
    schema = conn.metadata.table_schema(
        TableHandle("sq", "default", "orders2"))
    assert "o_orderkey" in schema.names


def test_roundtrip_matches_source(runner):
    want = runner.execute(
        "select n_nationkey, n_name from nation order by 1").rows
    got = runner.execute(
        "select n_nationkey, n_name from sq.default.nation2 order by 1"
    ).rows
    assert [(int(a), str(b)) for a, b in got] \
        == [(int(a), str(b)) for a, b in want]


def test_filter_pushdown_reaches_sqlite(runner):
    """The planner's bound tuples must render into sqlite's WHERE
    clause (reference JdbcMetadata.applyFilter -> QueryBuilder)."""
    conn = runner.session.catalogs.get("sq")
    split = conn.split_manager.splits(
        TableHandle("sq", "default", "orders2"), 1)[0]
    src = conn.page_source(split, ["o_orderkey", "o_totalprice"],
                           pushdown=(("o_orderkey", 10, 500),))
    assert '"o_orderkey" >= ?' in src._sql
    assert '"o_orderkey" <= ?' in src._sql
    n = sum(b.host_count() for b in src.batches())
    full = conn.page_source(split, ["o_orderkey"], pushdown=None)
    n_full = sum(b.host_count() for b in full.batches())
    assert 0 < n < n_full


def test_pushdown_in_explain(runner):
    out = runner.execute(
        "explain select o_totalprice from sq.default.orders2 "
        "where o_orderkey between 10 and 500")
    text = "\n".join(r[0] for r in out.rows)
    assert "sq.default.orders2" in text


def test_engine_filters_through_connector(runner):
    got = runner.execute(
        """select count(*), sum(o_totalprice) from sq.default.orders2
           where o_orderkey between 10 and 500""").rows
    want = runner.execute(
        """select count(*), sum(o_totalprice) from orders
           where o_orderkey between 10 and 500 and o_orderkey < 1000"""
    ).rows
    assert int(got[0][0]) == int(want[0][0])
    assert float(got[0][1]) == pytest.approx(float(want[0][1]), rel=1e-9)


def test_cross_catalog_join(runner):
    got = runner.execute(
        """select r_name, count(*) from sq.default.nation2
           join tpch.default.region on n_regionkey = r_regionkey
           group by r_name order by r_name""").rows
    assert len(got) == 5 and all(int(c) == 5 for _, c in got)


def test_stats_feed_optimizer(runner):
    conn = runner.session.catalogs.get("sq")
    stats = conn.metadata.table_stats(
        TableHandle("sq", "default", "nation2"))
    assert stats.row_count == 25
    cs = stats.columns["n_nationkey"]
    assert cs.distinct_count == 25 and cs.min_value == 0


def test_plugin_factory_loads_from_properties(tmp_path):
    from presto_tpu.config import CONNECTOR_FACTORIES
    db = str(tmp_path / "p.db")
    conn = CONNECTOR_FACTORIES["sqlite"]({"sqlite.path": db})
    conn.create_table("t", __import__(
        "presto_tpu.batch", fromlist=["Schema"]).Schema(
            [("a", __import__("presto_tpu", fromlist=["types"])
              .types.BIGINT)]))
    assert conn.metadata.list_tables() == ["t"]


def test_data_version_moves_on_a_foreign_commit_whatever_thread_asks(
        tmp_path):
    """A cache stamps ``data_version`` on one thread and revalidates on
    another (the statement server's pool). PRAGMA data_version is a
    setting of one connection, and a fresh one reads 1 whatever the
    file holds: read from a connection a thread, the stamp and the
    check agreed after a foreign commit and the stale entry was served
    (``test_fleet.py::test_dropped_broadcast_still_serves_correct_rows``,
    4 of 40 runs beside other load)."""
    import sqlite3
    import threading
    db = str(tmp_path / "v.db")
    raw = sqlite3.connect(db)
    raw.execute("create table t (x)")
    raw.commit()
    conn = SqliteConnector(db)
    seen = []

    def ask():
        seen.append(conn.data_version("t"))
    for foreign_commit in (False, True, False):
        if foreign_commit:
            raw.execute("insert into t values (1)")
            raw.commit()
        t = threading.Thread(target=ask)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert seen[0] != seen[1], "a foreign commit went unseen"
    assert seen[1] == seen[2], "the version moved with no commit"
