"""Distributed SQL execution on the virtual 8-device mesh vs LocalRunner.

Ring-3 of the test strategy (SURVEY.md §4): same queries, N shards of
SPMD programs with real collectives, results must match the single-device
path exactly.
"""
import pytest

from presto_tpu.exec.runner import LocalRunner

from tpch_queries import Q as TPCH_QUERIES

# minutes of shard_map compiles even with a warm persistent cache: out
# of the serial tier-1 time budget (run explicitly, or with xdist)
pytestmark = pytest.mark.slow

SF = 0.01

#: every TPC-H query the suite carries runs on the mesh — parity with
#: the local runner is the contract (any exclusion is a bug, not a
#: configuration)
DIST_QUERIES = list(TPCH_QUERIES)


@pytest.fixture(scope="module")
def local():
    return LocalRunner(tpch_sf=SF)


@pytest.fixture(scope="module")
def dist(local, mesh_runner):
    return mesh_runner(catalogs=local.session.catalogs,
                       rows_per_batch=1 << 13)


def _norm(rows, has_order):
    out = []
    for r in rows:
        nr = []
        for v in r:
            if hasattr(v, "item"):
                v = v.item()
            if isinstance(v, float):
                v = round(v, 4)
            nr.append(v)
        out.append(tuple(nr))
    return out if has_order else sorted(out, key=repr)


def check(local, dist, sql, rel=1e-9):
    want = local.execute(sql)
    got = dist.execute(sql)
    has_order = "order by" in sql.lower()
    w = _norm(want.rows, has_order)
    g = _norm(got.rows, has_order)
    assert len(g) == len(w), f"{len(g)} rows vs local {len(w)}"
    for gr, wr in zip(g, w):
        for gv, wv in zip(gr, wr):
            if isinstance(gv, float):
                assert gv == pytest.approx(wv, rel=rel, abs=1e-9), (gr, wr)
            else:
                assert gv == wv, (gr, wr)


@pytest.mark.parametrize(
    "name,sql,_o", DIST_QUERIES, ids=[t[0] for t in DIST_QUERIES])
def test_tpch_distributed(local, dist, name, sql, _o):
    check(local, dist, sql, rel=1e-6)


BASICS = [
    "select count(*) from lineitem",
    "select o_orderstatus, count(*), sum(o_totalprice) from orders group by 1 order by 1",
    "select n_name from nation where n_regionkey = 2 order by 1",
    "select distinct c_mktsegment from customer order by 1",
    "select o_orderkey, o_totalprice from orders order by o_totalprice desc limit 5",
    "select count(*) from orders where o_custkey not in (select c_custkey from customer where c_acctbal < 0)",
    "select s_name, n_name from supplier left join nation on s_nationkey = n_nationkey order by 1 limit 4",
]


@pytest.mark.parametrize("sql", BASICS, ids=range(len(BASICS)))
def test_basics_distributed(local, dist, sql):
    check(local, dist, sql)


def _with_props(runner, props):
    import contextlib

    @contextlib.contextmanager
    def cm():
        old = dict(runner.session.properties)
        runner.session.properties.update(props)
        try:
            yield
        finally:
            runner.session.properties.clear()
            runner.session.properties.update(old)
    return cm()


def test_partitioned_semi_distribution_parity(local, dist):
    """Forcing the stats-driven partitioned semi distribution (round 8:
    membership no longer broadcasts everywhere) keeps mesh results
    row-exact — both sides hash by key, per-shard verdicts compose."""
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer where c_nationkey < 7)")
    props = {"broadcast_join_row_limit": 10}
    with _with_props(local, props):
        want = local.execute(sql)
    with _with_props(dist, props):
        got = dist.execute(sql)
    assert want.rows == got.rows
    assert want.rows[0][0] > 0


def test_keyed_direct_join_mesh_parity(local, dist):
    """Planner key_bounds ride the mesh path: the per-shard build
    prepares a composite direct table once and every probe batch reuses
    it. join_dense_path=false must give identical rows."""
    sql = ("select n_name, count(*) from customer "
           "join nation on c_nationkey = n_nationkey "
           "group by n_name order by n_name")
    on = dist.execute(sql).rows
    with _with_props(dist, {"join_dense_path": False}):
        off = dist.execute(sql).rows
    assert on == off == local.execute(sql).rows
