"""Long decimals (precision 19..38) as two-limb int128 columns.

The reference models decimal(38) over Int128 (reference
presto-spi/.../spi/type/DecimalType.java MAX_PRECISION = 38,
spi/block/Int128ArrayBlock.java, UnscaledDecimal128Arithmetic.java);
here the storage is an [capacity, 2] i64 limb tile with vector kernels
(presto_tpu/ops/int128.py). Every result checks against the Python
``decimal.Decimal`` oracle.
"""
import decimal
from decimal import Decimal

import numpy as np
import pytest


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.001)


@pytest.fixture(scope="module")
def dist(mesh_runner):
    return mesh_runner(tpch_sf=0.001, n_devices=8,
                       rows_per_batch=1 << 10)


# -- kernel-level oracle ----------------------------------------------------

def _dec(pair):
    from presto_tpu.ops.int128 import int_of
    v = int_of(*pair)
    return v - 2 ** 128 if v >= 2 ** 127 else v


def test_int128_arith_oracle():
    import jax.numpy as jnp
    from presto_tpu.ops import int128 as I

    rng = np.random.default_rng(5)
    a_py = [int(rng.integers(-10 ** 18, 10 ** 18)) * 10 ** int(rng.integers(0, 19))
            + int(rng.integers(-10 ** 6, 10 ** 6)) for _ in range(300)]
    b_py = [int(rng.integers(-10 ** 18, 10 ** 18)) for _ in range(300)]
    a = jnp.asarray(I.np_limbs(a_py))
    b = jnp.asarray(I.np_limbs(b_py))
    s = np.asarray(I.add(a, b))
    d = np.asarray(I.sub(a, b))
    p, ovf = I.mul(a, b)
    p, ovf = np.asarray(p), np.asarray(ovf)
    lt = np.asarray(I.lt(a, b))
    for i in range(300):
        assert _dec(s[i]) == a_py[i] + b_py[i]
        assert _dec(d[i]) == a_py[i] - b_py[i]
        if abs(a_py[i] * b_py[i]) < 2 ** 127:
            assert not ovf[i] and _dec(p[i]) == a_py[i] * b_py[i], i
        assert bool(lt[i]) == (a_py[i] < b_py[i])


def test_int128_rescale_half_up():
    import jax.numpy as jnp
    from presto_tpu.ops import int128 as I

    vals = [123456789012345678901234567895, -123456789012345678901234567895,
            49, 50, -49, -50, 0]
    x = jnp.asarray(I.np_limbs(vals))
    down, _ = I.rescale(x, -2)
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        for i, v in enumerate(vals):
            want = int(Decimal(v).scaleb(-2).quantize(
                0, rounding=decimal.ROUND_HALF_UP))
            assert _dec(np.asarray(down)[i]) == want, (v, want)
    up, ovf = I.rescale(x, 8)
    assert _dec(np.asarray(up)[0]) == vals[0] * 10 ** 8
    assert not bool(np.asarray(ovf)[0])


def test_int128_digit_sums_exact():
    import jax.numpy as jnp
    from presto_tpu.ops import int128 as I

    rng = np.random.default_rng(6)
    vals = [int(rng.integers(-10 ** 18, 10 ** 18)) * 10 ** 19 + 7
            for _ in range(5000)]
    planes = I.digit_sum_tiles(jnp.asarray(I.np_limbs(vals)))
    total = I.from_digit_sum_tiles(jnp.sum(planes, axis=0))
    assert _dec(np.asarray(total)) == sum(vals)


# -- data plane -------------------------------------------------------------

def test_long_decimal_column_roundtrip():
    from presto_tpu.batch import Batch
    from presto_tpu import types as T

    t = T.DecimalType(38, 10)
    vals = [Decimal("12345678901234567890.0123456789"), None,
            Decimal("-9999999999999999999999999999.9999999999"),
            Decimal("0.5")]
    b = Batch.from_pydict({"d": (t, vals)})
    assert b.columns[0].data.shape == (128, 2)
    out = [r[0] for r in b.to_pylist()]
    assert out[0] == vals[0] and out[1] is None
    assert out[2] == vals[2]
    assert out[3] == Decimal("0.5000000000")


def test_long_decimal_wire_roundtrip():
    from presto_tpu.batch import Batch
    from presto_tpu import types as T
    from presto_tpu.exec import pages

    t = T.DecimalType(30, 4)
    vals = [Decimal("12345678901234567890.1234"), None, Decimal("-7.5")]
    b = Batch.from_pydict({"d": (t, vals)})
    blob = pages.serialize_page(b)
    back = pages.deserialize_page(blob)
    assert [r[0] for r in back.to_pylist()] == [r[0] for r in b.to_pylist()]


# -- SQL surface ------------------------------------------------------------

def test_literals_and_arith(runner):
    rows = runner.execute(
        "select decimal '12345678901234567890.12345' + "
        "decimal '98765432109876543210.5', "
        "decimal '99999999999999999999' * decimal '1000000000000000000', "
        "decimal '12345678901234567890.5' - decimal '0.5'").rows
    assert rows[0][0] == Decimal("111111111011111111100.62345")
    assert rows[0][1] == Decimal("99999999999999999999000000000000000000")
    assert rows[0][2] == Decimal("12345678901234567890.0")


def test_division_and_rounding(runner):
    rows = runner.execute(
        "select cast('12345678901234567890.5' as decimal(38,2)) / 4, "
        "round(decimal '12345678901234567890.567', 1), "
        "floor(decimal '-12345678901234567890.5'), "
        "ceil(decimal '-12345678901234567890.5')").rows
    assert rows[0][0] == Decimal("3086419725308641972.63")
    assert rows[0][1] == Decimal("12345678901234567890.600")
    assert rows[0][2] == Decimal("-12345678901234567891.0")
    assert rows[0][3] == Decimal("-12345678901234567890.0")


def test_comparisons_and_abs(runner):
    rows = runner.execute(
        "select decimal '12345678901234567890' > "
        "decimal '12345678901234567889', "
        "abs(decimal '-123456789012345678901'), "
        "sign(decimal '-123456789012345678901')").rows
    assert bool(rows[0][0]) is True
    assert rows[0][1] == Decimal("123456789012345678901")
    assert rows[0][2] == Decimal("-1")


def test_casts(runner):
    rows = runner.execute(
        "select cast(decimal '123456789012345678901.5' as double), "
        "cast(decimal '123.45678901234567890123' as decimal(10,2)), "
        "cast(12345 as decimal(38,3)), "
        "cast(decimal '42.0000000000000000000009' as bigint)").rows
    assert rows[0][0] == pytest.approx(1.2345678901234568e20)
    assert rows[0][1] == Decimal("123.46")
    assert rows[0][2] == Decimal("12345.000")
    assert rows[0][3] == 42


def test_overflow_errors(runner):
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError):
        runner.execute(
            "select decimal '99999999999999999999999999999999999999' "
            "+ decimal '1'")
    with pytest.raises(QueryError):
        runner.execute(
            "select cast(decimal '12345678901234567890' as integer)")


def test_literal_over_38_digits_rejected(runner):
    from presto_tpu.sql.analyzer import AnalysisError
    with pytest.raises(AnalysisError):
        runner.execute(
            "select decimal '999999999999999999999999999999999999990'")


def test_null_propagation(runner):
    rows = runner.execute(
        "select cast(null as decimal(38,2)) + decimal '1.00', "
        "coalesce(cast(null as decimal(30,1)), decimal '7.5')").rows
    assert rows[0][0] is None
    assert rows[0][1] == Decimal("7.5")


# -- aggregation vs Decimal oracle ------------------------------------------

def test_sum_widens_to_38(runner):
    """sum(decimal(p,s)) is decimal(38,s): short-decimal columns whose
    sums overflow 18 digits are exact (reference
    DecimalSumAggregation)."""
    rows = runner.execute(
        "select sum(x), avg(x), min(x), max(x) from (values "
        "decimal '999999999999999.99', decimal '999999999999999.99', "
        "decimal '-0.01', cast(null as decimal(17,2))) t(x)").rows
    assert rows[0][0] == Decimal("1999999999999999.97")
    assert rows[0][1] == Decimal("666666666666666.66")   # half-up /3
    assert rows[0][2] == Decimal("-0.01")
    assert rows[0][3] == Decimal("999999999999999.99")


def test_grouped_long_decimal_aggs(runner):
    rows = runner.execute(
        "select k, sum(x), min(x), max(x) from (values "
        "(1, decimal '99999999999999999999999999999999.99'), "
        "(1, decimal '0.01'), "
        "(2, decimal '-99999999999999999999999999999999.99'), "
        "(2, cast(null as decimal(34,2)))) t(k, x) "
        "group by k order by k").rows
    assert rows[0][1] == Decimal("100000000000000000000000000000000.00")
    assert rows[0][2] == Decimal("0.01")
    assert rows[0][3] == Decimal("99999999999999999999999999999999.99")
    assert rows[1][1] == Decimal("-99999999999999999999999999999999.99")


def test_group_by_and_order_by_long_decimal_key(runner):
    rows = runner.execute(
        "select x, count(*) from (values decimal '12345678901234567890.5', "
        "decimal '12345678901234567890.5', decimal '-1.0', "
        "cast(null as decimal(21,1))) t(x) group by x order by x desc").rows
    # DESC with NULLS FIRST (Presto default for desc)
    assert rows[0][0] is None
    assert rows[1] == (Decimal("12345678901234567890.5"), 2)
    assert rows[2] == (Decimal("-1.0"), 1)


def test_distinct_long_decimal(runner):
    rows = runner.execute(
        "select distinct x from (values decimal '1.00', decimal '1.00', "
        "decimal '99999999999999999999.99') t(x) order by x").rows
    assert [r[0] for r in rows] == [Decimal("1.00"),
                                    Decimal("99999999999999999999.99")]


def test_distributed_decimal_sum(dist, runner):
    """Partial decimal(38) limb states merge across the mesh exchange
    exactly (digit-plane sums are associative integers)."""
    q = ("select k, sum(x) from (values "
         "(1, decimal '9999999999999999.99'), (2, decimal '0.01'), "
         "(1, decimal '9999999999999999.99'), (2, decimal '5.00'), "
         "(1, decimal '0.02')) t(k, x) group by k order by k")
    assert dist.execute(q).rows == runner.execute(q).rows


def test_long_decimal_join_key(runner):
    """Equi-joins on long-decimal keys: limbs become two lexicographic
    key operands (regression: the [n,2] tile crashed lax.sort)."""
    rows = runner.execute(
        "with t as (select * from (values decimal '12345678901234567890.5', "
        "decimal '-1.0', decimal '99999999999999999999999999.25') v(q)) "
        "select count(*) from t a join t b on a.q = b.q").rows
    assert rows == [(3,)]


def test_window_over_long_decimal_rejected(runner):
    """Window aggregates over decimal(>18) raise a clear analysis error
    instead of producing corrupt cumsums."""
    from presto_tpu.sql.analyzer import AnalysisError
    with pytest.raises(AnalysisError):
        runner.execute(
            "select sum(cast(x as decimal(38,2))) over () from "
            "(values decimal '1.00') t(x)")


def test_window_sum_short_decimal_still_exact(runner):
    """Window sums over short decimals keep the exact i64 path and
    correct per-partition results (regression: the decimal(38) agg
    output type leaked into window specs and corrupted results)."""
    rows = runner.execute(
        "select k, sum(x) over (partition by k) from (values "
        "(1, decimal '1.50'), (1, decimal '2.00'), (2, decimal '5.00')) "
        "t(k, x) order by k").rows
    assert rows == [(1, Decimal("3.50")), (1, Decimal("3.50")),
                    (2, Decimal("5.00"))]


def test_sum_overflow_raises(runner):
    """A 38-digit sum overflow raises NUMERIC_VALUE_OUT_OF_RANGE at
    decode instead of wrapping silently."""
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError):
        runner.execute(
            "select sum(x) from (values "
            "decimal '99999999999999999999999999999999999999', "
            "decimal '99999999999999999999999999999999999999') t(x)")


def test_round_digits_beyond_scale_is_identity(runner):
    rows = runner.execute(
        "select round(decimal '9999999999999999999999999999999999', 10), "
        "round(decimal '123456789012345678.12', 5)").rows
    assert rows[0][0] == Decimal("9999999999999999999999999999999999")
    assert rows[0][1] == Decimal("123456789012345678.12")


def test_oracle_random_sums(runner):
    """Random 25-digit decimals: engine sum == Python Decimal sum."""
    rng = np.random.default_rng(17)
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        vals = [Decimal(int(rng.integers(-10 ** 15, 10 ** 15)))
                * Decimal(10) ** int(rng.integers(0, 10))
                + Decimal(int(rng.integers(0, 100))).scaleb(-2)
                for _ in range(97)]
        lits = ", ".join(f"decimal '{v}'" for v in vals)
        rows = runner.execute(
            f"select sum(x), min(x), max(x) from (values {lits}) t(x)").rows
        want_sum = sum(vals).quantize(Decimal("0.01"))
        assert rows[0][0] == want_sum, (rows[0][0], want_sum)
        assert rows[0][1] == min(vals).quantize(Decimal("0.01"))
        assert rows[0][2] == max(vals).quantize(Decimal("0.01"))


def test_wide_division_exact(runner):
    """General int128 division (float-estimate + exact correction,
    ops/int128.py divmod_abs) against python Decimal, including the
    small-divisor and small-value shapes that exposed the to_f64/
    from_f64 precision bugs."""
    import decimal as _d
    from decimal import ROUND_HALF_UP

    _d.getcontext().prec = 60
    cases = [
        ("12345678901234567890123456.78", "decimal(38,2)",
         "987654321098765.4", "decimal(16,1)"),
        ("99999999999999999999.99", "decimal(22,2)", "-3.7",
         "decimal(16,1)"),
        ("9955911909542365299945990106.63", "decimal(38,2)", "3.00",
         "decimal(18,2)"),
        ("0.04", "decimal(38,2)", "400000000000000000.0",
         "decimal(19,1)"),
    ]
    for a, ta, b, tb in cases:
        got = runner.execute(
            f"select cast('{a}' as {ta}) / cast('{b}' as {tb})"
        ).rows[0][0]
        scale = -got.as_tuple().exponent
        want = (Decimal(a) / Decimal(b)).quantize(
            Decimal(1).scaleb(-scale), rounding=ROUND_HALF_UP)
        assert got == want, (a, b, got, want)


def test_long_decimal_to_double_small_values(runner):
    """cast(decimal(38,s) as double) of SMALL magnitudes: the old
    to_f64 catastrophically cancelled (4.00 came back 0.0)."""
    rows = runner.execute(
        "select cast(cast('4.00' as decimal(38,2)) as double), "
        "cast(cast('-7.25' as decimal(20,2)) as double), "
        "cast(cast('0.01' as decimal(38,2)) as double)").rows
    assert rows[0] == (4.0, -7.25, 0.01)
