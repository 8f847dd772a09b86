"""Row-level error semantics: DIVISION_BY_ZERO, TRY, short-circuits.

Mirrors the reference's error behavior (reference
presto-spi/.../spi/StandardErrorCode.java, operator/scalar/TryFunction.java,
sql/gen/AndCodeGenerator short-circuit): integer/decimal division by zero
raises, double division follows IEEE, TRY() yields NULL, and branches that
are not taken never raise.
"""
import math

import pytest

from presto_tpu.errors import QueryError


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.001)


def q1(runner, sql):
    rows = runner.execute(sql).rows
    assert len(rows) == 1
    return rows[0][0]


def test_integer_division_by_zero(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select 1/0")


def test_modulus_by_zero(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select 5 % 0")


def test_division_by_zero_in_where(runner):
    # the predicate evaluates 1/l_x for every scanned row
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute(
            "select count(*) from lineitem "
            "where 1/(l_linenumber - l_linenumber) > 0")


def test_try_division_by_zero_is_null(runner):
    assert q1(runner, "select try(1/0)") is None


def test_try_passthrough(runner):
    assert q1(runner, "select try(6/2)") == 3


def test_double_division_ieee(runner):
    # Java/Presto DoubleOperators: x/0.0 = Infinity, no error
    assert math.isinf(q1(runner, "select 1e0/0e0"))
    assert math.isnan(q1(runner, "select 0e0/0e0"))


def test_and_short_circuit_suppresses_error(runner):
    n = q1(runner, "select count(*) from lineitem "
                   "where l_linenumber <> 0 and l_orderkey/l_linenumber > 0")
    assert n > 0


def test_or_short_circuit_suppresses_error(runner):
    n = q1(runner, "select count(*) from lineitem "
                   "where l_linenumber > 0 or 1/(l_linenumber*0) > 0")
    assert n > 0


def test_case_untaken_branch_no_error(runner):
    v = q1(runner, "select case when l_linenumber = 99 "
                   "then l_orderkey/(l_linenumber-l_linenumber) "
                   "else 1 end from lineitem limit 1")
    assert v == 1


def test_if_untaken_branch_no_error(runner):
    assert q1(runner, "select if(false, 1/0, 42)") == 42


def test_if_taken_branch_errors(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select if(true, 1/0, 42)")


def test_coalesce_error_propagates(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select coalesce(1/0, 7)")


def test_coalesce_of_try(runner):
    assert q1(runner, "select coalesce(try(1/0), 7)") == 7


def test_null_divisor_is_null_not_error(runner):
    # null arguments short-circuit the call (no evaluation, no error)
    assert q1(runner, "select 1/cast(null as bigint)") is None


def test_error_in_projection_over_table(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select l_orderkey/(l_linenumber - l_linenumber) "
                       "from lineitem")


def test_decimal_division_by_zero(runner):
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("select cast(1 as decimal(10,2)) / "
                       "cast(0 as decimal(10,2))")


def test_insert_error_persists_nothing(runner):
    # a failing INSERT ... SELECT must not write partial rows
    runner.execute("create table memory.default.err_t as select 1 as x")
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute("insert into memory.default.err_t "
                       "select l_linenumber/(l_linenumber-l_linenumber) "
                       "from lineitem")
    assert runner.execute(
        "select count(*) from memory.default.err_t").rows == [(1,)]


def test_join_residual_error(runner):
    # ON-clause residual errors raise like WHERE errors do
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute(
            "select count(*) from lineitem l join orders o "
            "on l.l_orderkey = o.o_orderkey "
            "and l.l_partkey > o.o_orderkey / o.o_shippriority")


def test_distributed_division_by_zero(mesh_runner):
    r = mesh_runner(tpch_sf=0.001, n_devices=8)
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        r.execute("select l_orderkey/(l_linenumber - l_linenumber) "
                  "from lineitem")
