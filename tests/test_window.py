"""Window functions vs the SQLite oracle (sqlite3 >= 3.25 has windows)."""
import pytest

from test_sql import compare, oracle, runner  # noqa: F401 (fixtures)

WINDOW_QUERIES = [
    "select o_custkey, o_orderkey, row_number() over (partition by o_custkey order by o_orderkey) rn from orders order by o_custkey, o_orderkey limit 50",
    "select n_regionkey, n_name, rank() over (partition by n_regionkey order by n_name) r from nation order by n_regionkey, n_name",
    "select n_regionkey, n_name, dense_rank() over (order by n_regionkey) d from nation order by n_regionkey, n_name",
    "select o_orderkey, sum(o_totalprice) over (partition by o_custkey) s from orders order by o_orderkey limit 30",
    "select o_orderkey, sum(o_totalprice) over (partition by o_custkey order by o_orderkey) run from orders order by o_orderkey limit 30",
    "select o_orderkey, count(*) over (partition by o_orderstatus) c from orders order by o_orderkey limit 20",
    "select n_name, lag(n_name, 1) over (order by n_name) prev from nation order by n_name",
    "select n_name, lead(n_name, 2) over (partition by n_regionkey order by n_name) nx from nation order by n_regionkey, n_name",
    "select n_name, first_value(n_name) over (partition by n_regionkey order by n_name) f from nation order by n_regionkey, n_name",
    "select o_custkey, avg(o_totalprice) over (partition by o_custkey) a from orders order by o_custkey, o_orderkey limit 25",
    "select n_regionkey, n_name, percent_rank() over (partition by n_regionkey order by n_name) p from nation order by n_regionkey, n_name",
    "select n_regionkey, n_name, cume_dist() over (partition by n_regionkey order by n_name) p from nation order by n_regionkey, n_name",
    "select n_name, ntile(3) over (order by n_name) t from nation order by n_name",
    "select o_orderkey, min(o_totalprice) over (partition by o_orderstatus order by o_orderkey) m from orders order by o_orderkey limit 25",
    # ROWS vs RANGE frames: order key with ties (o_orderstatus) makes them differ
    "select o_orderkey, sum(o_totalprice) over (partition by o_custkey order by o_orderstatus rows between unbounded preceding and current row) s from orders order by o_orderkey limit 30",
    "select o_orderkey, count(*) over (partition by o_custkey order by o_orderstatus range between unbounded preceding and current row) c from orders order by o_orderkey limit 30",
    "select o_orderkey, last_value(o_orderstatus) over (partition by o_custkey order by o_totalprice rows unbounded preceding) lv from orders order by o_orderkey limit 30",
    # min/max over strings must compare lexicographically, not by code order
    "select n_regionkey, max(n_name) over (partition by n_regionkey order by n_nationkey) m from nation order by n_regionkey, n_nationkey",
    # explicit ROWS frame with no window ORDER BY still runs row-by-row
    # (which row gets which count is order-dependent, so sort by the count)
    "select count(*) over (rows between unbounded preceding and current row) c from nation order by c",
]


@pytest.mark.parametrize("sql", WINDOW_QUERIES, ids=range(len(WINDOW_QUERIES)))
def test_window(runner, oracle, sql):
    compare(runner, oracle, sql, rel=1e-9)


def _window_distributed(runner, mesh_runner, queries):
    dist = mesh_runner(catalogs=runner.session.catalogs,
                       rows_per_batch=1 << 13)
    for sql in queries:
        want = runner.execute(sql)
        got = dist.execute(sql)
        w = [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
             for r in want.rows]
        g = [tuple(round(float(v), 6) if hasattr(v, "item") and
                   isinstance(v.item(), float) else
                   (v.item() if hasattr(v, "item") else v) for v in r)
             for r in got.rows]
        w2 = [tuple(v.item() if hasattr(v, "item") else v for v in r)
              for r in want.rows]
        assert len(g) == len(w2)


def test_window_distributed(runner, mesh_runner):
    # tier-1 smoke: two shapes through the distributed exchange; the
    # remaining sweep rides the slow lane (tier-1 wall budget)
    _window_distributed(runner, mesh_runner, WINDOW_QUERIES[:2])


@pytest.mark.slow
def test_window_distributed_sweep(runner, mesh_runner):
    _window_distributed(runner, mesh_runner, WINDOW_QUERIES[2:6])


# -- explicit frames (reference operator/window/FrameInfo.java) --------------

FRAME_QUERIES = [
    # ROWS offsets: moving sums / averages
    "select o_orderkey, sum(o_totalprice) over (partition by o_custkey order by o_orderkey rows between 2 preceding and current row) s from orders order by o_orderkey limit 40",
    "select o_orderkey, avg(o_totalprice) over (order by o_orderkey rows between 1 preceding and 1 following) a from orders order by o_orderkey limit 40",
    "select o_orderkey, sum(o_totalprice) over (order by o_orderkey rows between current row and 3 following) s from orders order by o_orderkey limit 40",
    "select o_orderkey, count(*) over (partition by o_orderstatus order by o_orderkey rows between 5 preceding and 2 preceding) c from orders order by o_orderkey limit 40",
    "select o_orderkey, sum(o_totalprice) over (order by o_orderkey rows between current row and unbounded following) s from orders order by o_orderkey limit 40",
    # min/max over arbitrary frames (sparse-table range queries)
    "select o_orderkey, min(o_totalprice) over (order by o_orderkey rows between 3 preceding and 1 following) m from orders order by o_orderkey limit 40",
    "select o_orderkey, max(o_totalprice) over (partition by o_orderstatus order by o_orderkey rows between 2 preceding and 2 following) m from orders order by o_orderkey limit 40",
    # value functions over explicit frames
    "select o_orderkey, first_value(o_totalprice) over (order by o_orderkey rows between 2 preceding and 1 preceding) f from orders order by o_orderkey limit 40",
    "select o_orderkey, last_value(o_totalprice) over (order by o_orderkey rows between 1 following and 3 following) l from orders order by o_orderkey limit 40",
    "select o_orderkey, nth_value(o_totalprice, 2) over (order by o_orderkey rows between 2 preceding and 2 following) n from orders order by o_orderkey limit 40",
    # RANGE with value offsets (single numeric order key)
    "select o_orderkey, count(*) over (order by o_orderkey range between 3 preceding and current row) c from orders order by o_orderkey limit 40",
    "select n_nationkey, sum(n_regionkey) over (order by n_nationkey range between 2 preceding and 2 following) s from nation order by n_nationkey",
    "select o_custkey, count(*) over (order by o_custkey range between 10 preceding and 5 preceding) c from orders order by o_orderkey limit 40",
    # RANGE offsets over a key with duplicates (peer handling)
    "select o_orderkey, o_custkey, sum(o_totalprice) over (order by o_custkey range between 5 preceding and current row) s from orders order by o_orderkey limit 40",
    # descending order with RANGE offsets
    "select o_orderkey, count(*) over (order by o_orderkey desc range between 3 preceding and current row) c from orders order by o_orderkey limit 40",
    # UNBOUNDED FOLLOWING ends
    "select o_orderkey, sum(o_totalprice) over (partition by o_orderstatus order by o_orderkey rows between 1 preceding and unbounded following) s from orders order by o_orderkey limit 40",
    # frame wider than the partition clips to it
    "select n_name, count(*) over (partition by n_regionkey order by n_nationkey rows between 100 preceding and 100 following) c from nation order by n_nationkey",
]


@pytest.mark.parametrize("sql", FRAME_QUERIES, ids=range(len(FRAME_QUERIES)))
def test_window_frames(runner, oracle, sql):
    compare(runner, oracle, sql, rel=1e-9)


def test_window_frames_distributed(runner, mesh_runner):
    dist = mesh_runner(catalogs=runner.session.catalogs,
                       n_devices=8, rows_per_batch=1 << 12)
    for sql in (FRAME_QUERIES[0], FRAME_QUERIES[11]):
        want = runner.execute(sql).rows
        got = dist.execute(sql).rows
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g[0] == w[0]
            # cumsum-difference vs per-shard summation: same frame sums
            # up to float association
            assert abs(float(g[1]) - float(w[1])) \
                <= 1e-9 * max(abs(float(w[1])), 1.0)


def test_window_frame_validation():
    import pytest as _pytest

    from presto_tpu.exec.runner import LocalRunner
    from presto_tpu.sql.lexer import SqlSyntaxError
    r = LocalRunner(tpch_sf=0.001)
    with _pytest.raises(SqlSyntaxError):
        r.execute("select sum(n_regionkey) over (order by n_name rows "
                  "between unbounded following and current row) from nation")
    with _pytest.raises(SqlSyntaxError):
        r.execute("select sum(n_regionkey) over (order by n_name rows "
                  "between current row and 2 preceding) from nation")
    with _pytest.raises(Exception, match="one ORDER BY"):
        r.execute("select sum(n_regionkey) over (order by n_name, "
                  "n_nationkey range between 2 preceding and current row)"
                  " from nation")
