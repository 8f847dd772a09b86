"""What DOUBLE is on the CPU, stated as tests, for the two departures
that README.md names on the TPU v5e (its f64 is a pair of f32):

- range: a DOUBLE sum there overflows far below f64's range (eight
  1e300 sum to inf, eight 1e-300 to 0.0; ``chip_smoke.double_probe``,
  PR 23). Here it is f64's own.
- ``cast(decimal COLUMN as double)`` divides on the device
  (``expr/functions.cast_val``), and the v5e's quotient is not always
  the double that the same value is when it comes from the host (a
  literal, folded at plan time, or a DOUBLE column), so a comparison
  for equality of the two can miss there
  (``tools/f64_divide_probe.py`` counts them; ISSUE 29). Here every
  quotient is the host's, bit for bit.
"""
import decimal

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import Batch, Schema
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.expr import compiler, ir


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.01)


@pytest.mark.parametrize("cell,want", [("1e300", 8e300), ("1e-300", 8e-300)])
def test_a_double_sum_keeps_f64s_range_on_the_cpu(runner, cell, want):
    [(got,)] = runner.execute(
        f"select sum({cell}) from nation where n_nationkey < 8").rows
    assert got == pytest.approx(want, rel=1e-15) and np.isfinite(got)


@pytest.mark.parametrize("scale", [1, 2, 3, 4])
def test_a_decimal_columns_cast_to_double_is_the_hosts_quotient(scale):
    unscaled = np.arange(10_000, dtype=np.int64)
    typ = T.DecimalType(6, scale)
    batch = Batch.from_arrays(Schema([("d", typ)]), [unscaled])
    fn = compiler.ExprCompiler().projection(
        [ir.cast(ir.InputRef(type=typ, index=0), T.DOUBLE)], ["c"],
        batch.schema)
    got = np.asarray(fn(batch).columns[0].data)[:unscaled.size]
    assert (got == unscaled / 10.0 ** scale).all()


def test_a_cast_decimal_column_equals_its_double_literal_on_the_cpu(runner):
    """The comparison that can miss on the v5e: a quotient computed on
    the device against the same value written as a DOUBLE. It missed
    here too while the divisor was a constant that XLA could see
    (``35 * 0.01`` is 0.35000000000000003): ``functions._divisor``."""
    cells = ", ".join(str(decimal.Decimal(k) / 100) for k in range(1, 100))
    rows = runner.execute(
        f"select count(*) from (values {cells}) t(d) "
        f"where cast(d as double) in ({cells.replace(', ', 'e0, ')}e0)").rows
    assert rows == [(99,)]
    assert runner.execute(
        "select count(*) from (values 0.34, 0.35, 0.36) t(d) "
        "where cast(d as double) = 0.35e0").rows == [(1,)]


@pytest.mark.parametrize("template", ["q1", "q3", "q6"])
def test_no_benchmark_cells_program_divides_a_decimal_or_takes_log2(
        runner, template):
    """``functions._divisor`` and ``log2`` are shared kernels that PR 29
    changed: no cell of BENCHMARK.json traces either (TPC-H's columns
    here are DOUBLE, BIGINT, DATE and VARCHAR), so no cell's programs
    or numbers moved with them."""
    import dataclasses
    import importlib.util
    import os
    import random
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "templates",
        template + ".py")
    spec = importlib.util.spec_from_file_location("tpl_" + template, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def exprs(node):
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            for x in (v if isinstance(v, tuple) else (v,)):
                if isinstance(x, ir.Expr):
                    yield from walk(x)
        for c in node.children:
            yield from exprs(c)

    def walk(e):
        yield e
        for c in e.children():
            yield from walk(c)

    plan = runner.plan(mod.SQL.format(**mod.draw(random.Random(29))))
    nodes = list(exprs(plan.root))
    assert nodes
    assert not [e for e in nodes if isinstance(e, ir.Call)
                and e.name == "log2"]
    assert not [e for e in nodes if isinstance(e, ir.Cast)
                and isinstance(e.arg.type, T.DecimalType)
                and isinstance(e.type, (T.DoubleType, T.RealType))]
