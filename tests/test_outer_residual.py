"""Residual (ON-clause) predicates on LEFT/FULL OUTER joins vs the
SQLite oracle: the filter gates matches but never drops probe rows, and
a FULL join's unmatched-build tail counts only residual-surviving
matches (reference operator/LookupJoinOperator.java +
sql/gen/JoinFilterFunctionCompiler.java)."""
import sqlite3

import pytest

# tier-1 budget: excluded from `pytest -m 'not slow'` — residual-join kernels compile-bound
# (see tools/check_tier1_time.py; ~42s)
pytestmark = pytest.mark.slow

from test_sql import compare, oracle, runner  # noqa: F401 (fixtures)

QUERIES = [
    # LEFT join, unique build, residual over both sides
    """select c_custkey, o_orderkey from customer
       left join orders on c_custkey = o_custkey
                       and o_totalprice > 150000
       order by c_custkey, o_orderkey""",
    # LEFT join residual referencing only the probe side
    """select c_custkey, count(o_orderkey) from customer
       left join orders on c_custkey = o_custkey and c_acctbal > 0
       group by c_custkey order by c_custkey""",
    # LEFT join, multi-match build (orders per cust), arithmetic residual
    """select o_orderkey, l_linenumber from orders
       left join lineitem on o_orderkey = l_orderkey
                         and l_quantity * 2 > 60
       order by o_orderkey, l_linenumber""",
    # FULL join with residual: both null-extension sides must honor it
    """select n_name, s_name from nation
       full outer join supplier on n_nationkey = s_nationkey
                               and s_acctbal > 4000
       order by n_name nulls last, s_name nulls last""",
    # residual that is never true: LEFT degenerates to all-null payload
    """select c_custkey, o_orderkey from customer
       left join orders on c_custkey = o_custkey and 1 = 0
       order by c_custkey limit 50""",
]


@pytest.mark.parametrize("sql", QUERIES, ids=range(len(QUERIES)))
def test_outer_residual_matches_oracle(runner, oracle, sql):
    if "full outer" in sql and sqlite3.sqlite_version_info < (3, 39):
        # the ORACLE can't check this one: sqlite grew FULL OUTER JOIN
        # in 3.39 (the engine side is covered by
        # test_outer_residual_distributed and test_full_outer.py)
        pytest.skip("oracle sqlite < 3.39 lacks FULL OUTER JOIN")
    compare(runner, oracle, sql, rel=1e-9)


def test_outer_residual_distributed(runner, mesh_runner):
    dist = mesh_runner(catalogs=runner.session.catalogs,
                       n_devices=8, rows_per_batch=1 << 12)
    for sql in (QUERIES[0], QUERIES[3]):
        want = runner.execute(sql).rows
        got = dist.execute(sql).rows
        assert got == want


def test_outer_residual_under_spill(runner):
    """Partitioned (spilled-build) probing keeps outer+residual
    semantics: each probe row hashes to one partition."""
    from presto_tpu.exec.runner import LocalRunner
    r = LocalRunner(catalogs=runner.session.catalogs,
                    rows_per_batch=1 << 12)
    r.session.properties["query_max_memory"] = 200_000
    r.session.properties["spill_partitions"] = 4
    sql = """select o_orderkey, count(l_linenumber) c from orders
             left join lineitem on o_orderkey = l_orderkey
                               and l_quantity > 25
             group by o_orderkey order by o_orderkey limit 100"""
    want = runner.execute(sql).rows
    got = r.execute(sql).rows
    assert got == want
    stats = r.session.last_memory_stats
    assert stats.spilled_bytes > 0
