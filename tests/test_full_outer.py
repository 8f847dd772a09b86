"""FULL OUTER JOIN + arbitrary-arity join keys.

Reference: operator/LookupJoinOperator.java probes all join types against
the same lookup source, with LookupOuterOperator emitting the
unmatched-build tail from a visited-positions bitmap; join keys are
arbitrary channel tuples (sql/gen/JoinCompiler.java). The TPU engine
mirrors both: build_match_mask tracks matched build rows across probe
batches, and key tuples compare lexicographically at any arity/width.
"""
import pytest

# tier-1 budget: excluded from `pytest -m 'not slow'` — FULL OUTER matrix is compile-bound
# (see tools/check_tier1_time.py; ~55s)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.01)


@pytest.fixture(scope="module")
def dist(runner, mesh_runner):
    return mesh_runner(catalogs=runner.session.catalogs,
                       n_devices=8, rows_per_batch=1 << 12)


FULL_BASIC = """
SELECT a.x, a.v, b.x, b.w FROM
 (VALUES (1, 'a1'), (2, 'a2'), (4, 'a4')) a(x, v)
 FULL OUTER JOIN (VALUES (2, 'b2'), (3, 'b3'), (4, 'b4')) b(x, w)
 ON a.x = b.x
ORDER BY coalesce(a.x, b.x), a.v NULLS LAST
"""

FULL_EXPECT = [
    (1, "a1", None, None),
    (2, "a2", 2, "b2"),
    (None, None, 3, "b3"),
    (4, "a4", 4, "b4"),
]


def test_full_outer_basic(runner):
    assert runner.execute(FULL_BASIC).rows == FULL_EXPECT


def test_full_outer_distributed(dist):
    assert dist.execute(FULL_BASIC).rows == FULL_EXPECT


def test_full_outer_null_keys_never_match(runner):
    rows = runner.execute("""
        SELECT a.v, b.w FROM
         (VALUES (1, 'a1'), (cast(null as integer), 'an')) a(x, v)
         FULL OUTER JOIN
         (VALUES (1, 'b1'), (cast(null as integer), 'bn')) b(x, w)
         ON a.x = b.x
        ORDER BY a.v NULLS LAST, b.w NULLS LAST
    """).rows
    assert rows == [("a1", "b1"), ("an", None), (None, "bn")]


def test_full_outer_many_to_many(runner):
    rows = runner.execute("""
        SELECT a.v, b.w FROM
         (VALUES (1, 'a1'), (1, 'a2'), (5, 'a5')) a(x, v)
         FULL OUTER JOIN
         (VALUES (1, 'b1'), (1, 'b2'), (7, 'b7')) b(x, w)
         ON a.x = b.x
        ORDER BY a.v NULLS LAST, b.w NULLS LAST
    """).rows
    assert rows == [
        ("a1", "b1"), ("a1", "b2"), ("a2", "b1"), ("a2", "b2"),
        ("a5", None), (None, "b7"),
    ]


def test_full_outer_aggregate_over_tpch(runner):
    # every order has a customer, but not every customer has orders: the
    # unmatched-customer tail must survive the FULL join
    rows = runner.execute("""
        SELECT count(o.o_orderkey), count(*) FROM
        orders o FULL OUTER JOIN customer c ON o.o_custkey = c.c_custkey
    """).rows
    n_orders = runner.execute("SELECT count(*) FROM orders").rows[0][0]
    n_cust_without = runner.execute("""
        SELECT count(*) FROM customer c WHERE NOT EXISTS
         (SELECT 1 FROM orders o WHERE o.o_custkey = c.c_custkey)
    """).rows[0][0]
    assert rows[0][0] == n_orders
    assert rows[0][1] == n_orders + n_cust_without


def test_three_key_join(runner):
    rows = runner.execute("""
        SELECT a.v, b.w FROM
         (VALUES (9223372036854775806, 2.5, 1, 10),
                 (1, -0.0, 2, 20),
                 (5, 3.25, 3, 30)) a(x, y, z, v)
         JOIN (VALUES (9223372036854775806, 2.5, 1, 'hit1'),
                      (1, 0.0, 2, 'hit2'),
                      (5, 3.5, 3, 'miss')) b(x, y, z, w)
         ON a.x = b.x AND a.y = b.y AND a.z = b.z
        ORDER BY a.v
    """).rows
    assert rows == [(10, "hit1"), (20, "hit2")]


def test_wide_key_join_no_32bit_pack(runner):
    # both key columns span > 32 bits: the old shifted pack would collide
    rows = runner.execute("""
        SELECT a.v, b.w FROM
         (VALUES (4294967296123, 8589934592456, 1)) a(x, y, v)
         JOIN (VALUES (4294967296123, 8589934592456, 'hit'),
                      (4294967296123, 8589934592457, 'miss')) b(x, y, w)
         ON a.x = b.x AND a.y = b.y
    """).rows
    assert rows == [(1, "hit")]


def test_full_outer_spilled_build(runner):
    """Force the build side through the host-partition spill path."""
    from presto_tpu.exec.runner import LocalRunner
    r = LocalRunner(catalogs=runner.session.catalogs,
                    rows_per_batch=1 << 12)
    r.session.properties["query_max_memory"] = 200_000
    r.session.properties["spill_partitions"] = 4
    got = r.execute("""
        SELECT count(o.o_orderkey), count(*) FROM
        orders o FULL OUTER JOIN customer c ON o.o_custkey = c.c_custkey
    """).rows
    want = runner.execute("""
        SELECT count(o.o_orderkey), count(*) FROM
        orders o FULL OUTER JOIN customer c ON o.o_custkey = c.c_custkey
    """).rows
    assert got == want
    assert r.session.last_memory_stats is not None


def test_skewed_many_to_many_join(runner):
    """One key with multiplicity far above SKEW_MATCH_LIMIT must not
    explode expand_join's capacity; the executor chunks the build."""
    n = 300   # > SKEW_MATCH_LIMIT
    vals = ", ".join(f"(1, {i})" for i in range(n)) + ", (2, 9000)"
    rows = runner.execute(f"""
        SELECT a.x, count(*), sum(b.i) FROM
         (VALUES (1), (1), (2), (3)) a(x)
         JOIN (VALUES {vals}) b(x, i) ON a.x = b.x
        GROUP BY a.x ORDER BY a.x
    """).rows
    assert rows == [(1, 2 * n, 2 * sum(range(n))), (2, 1, 9000)]


def test_skewed_left_join_unmatched_once(runner):
    n = 200
    vals = ", ".join(f"(1, {i})" for i in range(n))
    rows = runner.execute(f"""
        SELECT a.x, count(b.i) FROM
         (VALUES (1), (5)) a(x)
         LEFT JOIN (VALUES {vals}) b(x, i) ON a.x = b.x
        GROUP BY a.x ORDER BY a.x
    """).rows
    assert rows == [(1, n), (5, 0)]
