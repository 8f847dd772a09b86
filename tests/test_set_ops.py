"""INTERSECT / EXCEPT (lowered to union-all + marker aggregation, the
reference's ImplementIntersectAsUnion.java / ImplementExceptAsUnion.java
rewrite)."""
import pytest


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.01)


@pytest.fixture(scope="module")
def dist(runner, mesh_runner):
    return mesh_runner(catalogs=runner.session.catalogs,
                       n_devices=8, rows_per_batch=1 << 12)


def test_intersect(runner):
    rows = runner.execute(
        "SELECT * FROM (VALUES 1,2,3,3) INTERSECT "
        "SELECT * FROM (VALUES 2,3,4) ORDER BY 1").rows
    assert rows == [(2,), (3,)]


def test_except(runner):
    rows = runner.execute(
        "SELECT * FROM (VALUES 1,2,3,3) EXCEPT "
        "SELECT * FROM (VALUES 2,4) ORDER BY 1").rows
    assert rows == [(1,), (3,)]


def test_except_multi_column(runner):
    rows = runner.execute(
        "SELECT * FROM (VALUES (1,'a'),(2,'b')) EXCEPT "
        "SELECT * FROM (VALUES (2,'b'),(3,'c'))").rows
    assert rows == [(1, "a")]


def test_intersect_null_equality(runner):
    # set-op semantics treat NULLs as equal (IS NOT DISTINCT), unlike =
    rows = runner.execute(
        "SELECT * FROM (VALUES 1, cast(null as integer)) INTERSECT "
        "SELECT * FROM (VALUES cast(null as integer), 2)").rows
    assert rows == [(None,)]


def test_intersect_binds_tighter_than_union(runner):
    rows = runner.execute(
        "SELECT * FROM (VALUES 1,2) UNION SELECT * FROM (VALUES 3,5) "
        "INTERSECT SELECT * FROM (VALUES 3) ORDER BY 1").rows
    assert rows == [(1,), (2,), (3,)]


def test_except_left_assoc_with_union(runner):
    # A UNION B EXCEPT C == (A UNION B) EXCEPT C
    rows = runner.execute(
        "SELECT * FROM (VALUES 1,2) UNION SELECT * FROM (VALUES 3) "
        "EXCEPT SELECT * FROM (VALUES 2) ORDER BY 1").rows
    assert rows == [(1,), (3,)]


def test_intersect_over_tpch(runner):
    got = runner.execute(
        "SELECT c_nationkey FROM customer INTERSECT "
        "SELECT s_nationkey FROM supplier ORDER BY 1").rows
    want = runner.execute(
        "SELECT DISTINCT c_nationkey FROM customer "
        "WHERE c_nationkey IN (SELECT s_nationkey FROM supplier) "
        "ORDER BY 1").rows
    assert got == want


def test_except_distributed(dist):
    rows = dist.execute(
        "SELECT * FROM (VALUES 1,2,3,3) EXCEPT "
        "SELECT * FROM (VALUES 2,4) ORDER BY 1").rows
    assert rows == [(1,), (3,)]


def test_intersect_all_rejected(runner):
    from presto_tpu.errors import QueryError
    from presto_tpu.sql.analyzer import AnalysisError
    with pytest.raises((AnalysisError, QueryError, NotImplementedError)):
        runner.execute("SELECT * FROM (VALUES 1) INTERSECT ALL "
                       "SELECT * FROM (VALUES 1)")
