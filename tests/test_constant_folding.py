"""ISSUE 29: literal expressions are folded on the host at plan time.

- every literal-only subtree of an optimized plan is ONE ``ir.Literal``
  (decimal arithmetic then a cast to double, date and interval
  arithmetic, nested casts, NULLs, boolean forms), and the folded value
  is, bit for bit, what the engine's own jitted program gives on the
  CPU for the same expression;
- a subtree whose evaluation raises is left alone: it raises as before
  over a table with rows and not over an empty one;
- a non-deterministic call is never folded;
- TPC-H Q6 for each of its eight DISCOUNT values equals a plain NumPy
  reference, with ``plan_template_cache`` off and on, and no constant
  subtree reaches a device program either way;
- bindings that differ only in literals share ONE scan-cache entry per
  split where the connector ignores the pushdown, and keep their own
  where it applies it.
"""
import datetime
import decimal
import importlib.util
import os
import random
import struct
import sys

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import Batch, Schema
from presto_tpu.connectors.spi import CatalogManager, TableHandle
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.errors import QueryError
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.exec.scancache import CACHE
from presto_tpu.expr import compiler, ir
from presto_tpu.expr.rewrite import constant_subtrees
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.planner.fold import Folder, fold_expr
from presto_tpu.planner.plan import FilterNode, TableScanNode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
D = decimal.Decimal
SF = 0.01


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=SF)


def _exprs(node):
    """Every expression of every node of a plan."""
    import dataclasses
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, ir.Expr):
            yield v
        elif isinstance(v, tuple):
            yield from (x for x in v if isinstance(x, ir.Expr))
    for c in node.children:
        yield from _exprs(c)


def _literals(e):
    if isinstance(e, ir.Literal):
        yield e
    for c in e.children():
        yield from _literals(c)


def _nodes(node, kind):
    if isinstance(node, kind):
        yield node
    for c in node.children:
        yield from _nodes(c, kind)


# -- each form folds to ONE literal -------------------------------------------

FORMS = [
    # decimal arithmetic is exact, then ONE conversion to double
    ("cast(0.06 - 0.01 as double)", T.DOUBLE, 0.05),
    ("cast(0.06 + 0.01 as double)", T.DOUBLE, 0.07),
    ("cast(0.03 * 1.5 as double)", T.DOUBLE, 0.045),
    ("0.06 - 0.01", T.DecimalType(3, 2), D("0.05")),
    # dates and intervals
    ("date '1994-01-01' + interval '1' year", T.DATE, "1995-01-01"),
    ("date '1994-03-31' - interval '1' month", T.DATE, "1994-02-28"),
    ("date '1998-12-01' - interval '90' day", T.DATE, "1998-09-02"),
    ("date '1996-02-29' + interval '1' year", T.DATE, "1997-02-28"),
    # nested casts
    ("cast(cast(2.5 as double) as bigint)", T.BIGINT, 3),
    ("cast(cast(7 as double) as decimal(5,2))", T.DecimalType(5, 2),
     D("7.00")),
    # NULL in, NULL out
    ("cast(null as bigint) + 1", T.BIGINT, None),
    ("cast(cast(null as decimal(4,2)) as double)", T.DOUBLE, None),
    # boolean forms over literals
    ("1 < 2 and 3 < 4", T.BOOLEAN, True),
    ("1 > 2 or cast(null as boolean)", T.BOOLEAN, None),
    ("2 between 1 and 3", T.BOOLEAN, True),
    ("coalesce(cast(null as bigint), 5)", T.BIGINT, 5),
    ("case when 1 = 2 then 10 else 20 end", T.BIGINT, 20),
    ("3 in (1, 2, 3)", T.BOOLEAN, True),
    ("not (1 = 1)", T.BOOLEAN, False),
]


@pytest.mark.parametrize("sql,typ,value", FORMS,
                         ids=[f[0] for f in FORMS])
def test_form_folds_to_one_literal(runner, sql, typ, value):
    plan = runner.plan(f"select {sql} as c, n_nationkey from nation")
    exprs = list(_exprs(plan.root))
    assert not [s for e in exprs for s in constant_subtrees(e)]
    lits = [x for e in exprs for x in _literals(e)]
    assert [(x.type, x.value) for x in lits] == [(typ, value)]
    assert type(lits[0].value) is type(value)
    # and the answer is that value on every row
    got = {r[0] for r in runner.execute(
        f"select {sql} as c, n_nationkey from nation").rows}
    want = (datetime.date.fromisoformat(value)
            if isinstance(typ, T.DateType) else value)
    assert got == {want}


def test_q6_plan_holds_its_bounds_as_literals(runner):
    q6 = _template("q6")
    plan = runner.plan(q6.SQL.format(DATE="1994-01-01", DISCOUNT="0.06",
                                     QUANTITY="24"))
    [flt] = list(_nodes(plan.root, FilterNode))
    text = repr(flt.predicate)
    assert "between(#2:double, lit(0.05:double), lit(0.07:double))" in text
    assert "lt(#3:date, lit('1995-01-01':date))" in text
    assert not constant_subtrees(flt.predicate)
    [scan] = list(_nodes(plan.root, TableScanNode))
    lo = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    hi = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    assert scan.pushdown == (("l_shipdate", lo, hi),)


def test_q1_plan_holds_a_literal_date(runner):
    q1 = _template("q1")
    plan = runner.plan(q1.SQL.format(DELTA="90"))
    [flt] = list(_nodes(plan.root, FilterNode))
    assert repr(flt.predicate) == "le(#6:date, lit('1998-09-02':date))"
    [scan] = list(_nodes(plan.root, TableScanNode))
    assert scan.pushdown == (("l_shipdate", None, 10471),)


def test_the_fold_is_counted_and_costs_a_span(runner):
    from presto_tpu.obs.trace import TRACER
    q6 = _template("q6")
    f0 = REGISTRY.value("plan_literals_folded_total")
    TRACER.clear()
    TRACER.enable(True)
    try:
        with TRACER.span("plan"):
            runner.plan(q6.SQL.format(DATE="1993-01-01", DISCOUNT="0.04",
                                      QUANTITY="25"))
        spans = TRACER.export()
    finally:
        TRACER.enable(False)
        TRACER.clear()
    # the discount's two bounds and the date's upper bound
    assert REGISTRY.value("plan_literals_folded_total") - f0 == 3
    [plan] = [s for s in spans if s["name"] == "plan"]
    folds = [s for s in spans if s["name"] == "fold"]
    assert folds and all(s["parentId"] == plan["spanId"] for s in folds)


def test_no_session_property_was_added():
    from presto_tpu.config import SESSION_PROPERTIES
    assert not [p for p in SESSION_PROPERTIES if "fold" in p]


# -- the folder against the engine's own evaluation ---------------------------

def _engine_value(e):
    """``e`` through the engine's jitted projection on the CPU, over a
    batch of one row."""
    fn = compiler.ExprCompiler().projection([e], ["c"], Schema([]))
    one_row = Batch(Schema([]), [], np.ones(1, dtype=bool))
    return fn(one_row).to_pylist()[0][0]


def _bits(v):
    return struct.pack("<d", v) if isinstance(v, float) else v


def _random_pairs():
    rng = random.Random(29)
    dec = lambda p, s: T.DecimalType(p, s)  # noqa: E731
    cases = []
    for i in range(12):
        a, b = rng.randint(1, 99), rng.randint(1, 99)
        op = ("add", "subtract", "multiply")[i % 3]
        out = dec(3, 2) if op != "multiply" else dec(4, 4)
        cases.append(ir.cast(ir.call(
            op, out, ir.lit(D(a) / 100, dec(2, 2)),
            ir.lit(D(b) / 100, dec(2, 2))), T.DOUBLE))
    for i in range(8):
        a, b = rng.uniform(-1e6, 1e6), rng.uniform(1e-3, 1e3)
        op = ("add", "subtract", "multiply", "divide")[i % 4]
        cases.append(ir.call(op, T.DOUBLE, ir.lit(a, T.DOUBLE),
                             ir.lit(b, T.DOUBLE)))
    for i in range(8):
        cases.append(ir.cast(ir.lit(D(rng.randint(0, 10 ** 9)) / 10 ** 4,
                                    dec(13, 4)), T.DOUBLE))
    for i in range(6):
        day = datetime.date(1992, 1, 1) + datetime.timedelta(
            days=rng.randint(0, 2500))
        fn = ("date_add_days", "date_add_months", "date_add_years")[i % 3]
        cases.append(ir.call(fn, T.DATE, ir.lit(day.isoformat(), T.DATE),
                             ir.lit(rng.randint(-40, 40), T.BIGINT)))
    return cases


@pytest.mark.parametrize("e", _random_pairs(), ids=repr)
def test_folded_value_is_the_engines_bit_for_bit(e):
    folded = fold_expr(e)
    assert isinstance(folded, ir.Literal) and folded.type == e.type
    want = _engine_value(e)
    if isinstance(e.type, T.DateType):
        want = want.isoformat()
    assert _bits(folded.value) == _bits(want)
    if isinstance(e, ir.Cast) and isinstance(e.arg, ir.Call):
        # ONE correctly rounded conversion of the exact decimal result
        a, b = (x.value for x in e.arg.args)
        exact = {"add": a + b, "subtract": a - b,
                 "multiply": a * b}[e.arg.name]
        assert folded.value == float(exact)


# -- what the fold leaves alone ------------------------------------------------

RAISING = [
    ("1 / 0", "DIVISION_BY_ZERO"),
    ("5 % 0", "DIVISION_BY_ZERO"),
    ("cast(12345678901234567890.5 as decimal(5,2))",
     "NUMERIC_VALUE_OUT_OF_RANGE"),
]


@pytest.mark.parametrize("sql,error", RAISING, ids=[r[0] for r in RAISING])
def test_a_raising_subtree_is_left_and_raises_at_run_time(runner, sql,
                                                          error):
    d0 = REGISTRY.value("plan_fold_declined_total")
    plan = runner.plan(f"select {sql} as c from nation")
    assert REGISTRY.value("plan_fold_declined_total") - d0 == 1
    left = [s for e in _exprs(plan.root) for s in constant_subtrees(e)]
    assert len(left) == 1
    with pytest.raises(QueryError, match=error):
        runner.execute(f"select {sql} as c from nation")
    # a row error: no row, no error
    assert runner.execute(
        f"select {sql} as c from nation where n_nationkey < 0").rows == []
    # what was left is counted where it is traced
    c0 = REGISTRY.value("expr_device_constant_total")
    runner.execute(f"select {sql} as c, n_name from nation "
                   f"where n_nationkey < 0")
    assert REGISTRY.value("expr_device_constant_total") - c0 >= 1


def test_the_part_that_folds_beside_one_that_raises(runner):
    plan = runner.plan("select 1 / 0 + (2 + 3) as c from nation")
    [left] = [s for e in _exprs(plan.root) for s in constant_subtrees(e)]
    assert repr(left) == ("add(divide(lit(1:bigint), lit(0:bigint)), "
                          "lit(5:bigint))")


def test_a_fold_that_cannot_reach_the_host_fails_the_query(
        runner, monkeypatch):
    """No CPU backend (``JAX_PLATFORMS`` naming the accelerator alone)
    is ``jax.devices("cpu")`` raising RuntimeError: it must not pass
    for a fold that declined, or every literal goes back to the device
    and the v5e answers Q6 wrongly again with a counter as the only
    sign."""
    def no_cpu(expr):
        raise RuntimeError("Unknown backend cpu")
    monkeypatch.setattr(compiler, "host_value", no_cpu)
    with pytest.raises(RuntimeError, match="Unknown backend"):
        runner.plan("select count(*) from nation where n_nationkey < 2 + 3")


@pytest.fixture
def plugin_functions():
    """``random(n)``, which a plugin may register (the engine has no
    non-deterministic function of its own), and the same body under a
    name that promises nothing."""
    from presto_tpu.expr import functions as F

    def impl(args, out_type):
        return F.Val(args[0].data * 0 + 7, args[0].valid, T.BIGINT)
    for name in ("random", "seven"):
        F.register_external(name, impl, lambda types: T.BIGINT)
    yield
    for name in ("random", "seven"):
        F._REGISTRY.pop(name)
        F._EXTERNAL_SIGNATURES.pop(name)


def test_a_non_deterministic_call_is_not_folded(runner, plugin_functions):
    plan = runner.plan("select random(5) + 1 as c from nation")
    calls = [repr(e) for e in _exprs(plan.root) if isinstance(e, ir.Call)]
    assert calls == ["add(random(lit(5:bigint)), lit(1:bigint))"]
    assert not [s for e in _exprs(plan.root) for s in constant_subtrees(e)]
    plan = runner.plan("select seven(5) + 1 as c from nation")
    assert [x.value for e in _exprs(plan.root)
            for x in _literals(e)] == [8]


@pytest.mark.parametrize("template_cache", [False, True])
@pytest.mark.parametrize("sql,want", [
    ("select count(*) from nation where n_nationkey < "
     "(select max(n_nationkey) from nation) * 0.5", 12),
    # beside it a literal that the template walk punches, and one that
    # it punches and the analyzer coerces (bigint to the double column)
    ("select count(*) from nation where n_nationkey < "
     "(select max(n_nationkey) from nation) * 0.5 and n_regionkey >= 1",
     10),
    ("select count(*) from lineitem where l_quantity < 24 and l_orderkey < "
     "(select max(o_orderkey) from orders) * 0.5 "
     "and l_discount between 0.06 - 0.01 and 0.06 + 0.01", None),
])
def test_an_init_plans_value_is_folded_when_it_arrives(
        runner, sql, want, template_cache):
    """``(select ...) * 0.5``: the scalar is known only at run time;
    the executor folds once it is (exec/local.py ``_resolve``), into a
    literal whether or not the plan holds parameters."""
    props = {"plan_template_cache": template_cache}
    if want is None:
        [(want,)] = runner.execute(
            sql.replace("0.06 - 0.01", "0.05e0").replace(
                "0.06 + 0.01", "0.07e0").replace(
                "(select max(o_orderkey) from orders) * 0.5",
                "7500")).rows
    c0 = REGISTRY.value("expr_device_constant_total")
    for _ in range(2):          # the template's build, then its reuse
        assert runner.execute(sql, properties=props).rows == [(want,)]
    assert REGISTRY.value("expr_device_constant_total") == c0


# -- plan templates: no parameter's arithmetic, no parameter's cast ------------

def _template_plan(runner, sql):
    from presto_tpu.serving.plancache import parse_cached
    from presto_tpu.serving.template import parameterize
    from presto_tpu.planner.optimizer import optimize
    from presto_tpu.planner.planner import plan_query
    _t, marked, values = parameterize(parse_cached(sql))
    return optimize(plan_query(marked, runner.session),
                    runner.session), values


def test_template_arithmetic_is_not_punched_and_folds(runner):
    plan, values = _template_plan(
        runner, "select count(*) from lineitem where l_quantity < 24 "
        "and l_discount between 0.06 - 0.01 and 0.06 + 0.01")
    assert values == {0: 24}
    [flt] = list(_nodes(plan.root, FilterNode))
    assert "between(#" in repr(flt.predicate)
    assert ("lit(0.05:double), lit(0.07:double)") in repr(flt.predicate)
    assert not constant_subtrees(flt.predicate)


@pytest.mark.parametrize("column,literal,typ", [
    ("l_quantity", "24", "double"),         # bigint to the double column
    ("l_discount", "0.05", "double"),       # decimal(3,2) to double
    ("l_orderkey", "24", "bigint"),         # as it is
])
def test_a_coerced_template_parameter_is_retyped_not_cast(
        runner, column, literal, typ):
    """A cast of a parameter would run on the device: the parameter
    takes the column's type and its binding converts on the host."""
    plan, values = _template_plan(
        runner, f"select count(*) from lineitem where {column} <= {literal}")
    [flt] = list(_nodes(plan.root, FilterNode))
    assert f"?0:{typ})" in repr(flt.predicate)
    assert not constant_subtrees(flt.predicate)
    sql = f"select count(*) from lineitem where {column} <= %s"
    props = {"plan_template_cache": True}
    for v in (literal, "0.07" if "." in literal else "30"):
        assert (runner.execute(sql % v, properties=props).rows
                == runner.execute(sql % v).rows)


def test_a_template_parameter_coerced_to_a_wider_decimal_is_retyped(runner):
    sql = ("select count(*) from (values 0.34, 0.35, 0.36, 1.25) t(d) "
           "where d >= %s")
    c0 = REGISTRY.value("expr_device_constant_total")
    for v, want in (("0.35", 3), ("0.4", 1), ("0.36", 2)):
        assert runner.execute(
            sql % v, properties={"plan_template_cache": True}).rows \
            == [(want,)]
    assert REGISTRY.value("expr_device_constant_total") == c0


def test_template_binding_whose_arithmetic_raises_plans_alone(runner):
    sql = "select count(*) from nation where n_nationkey < 100 / %d"
    props = {"plan_template_cache": True}
    assert runner.execute(sql % 4, properties=props).rows == [(25,)]
    with pytest.raises(QueryError, match="DIVISION_BY_ZERO"):
        runner.execute(sql % 0, properties=props)
    assert runner.execute(sql % 10, properties=props).rows == [(10,)]


# -- TPC-H Q6, every DISCOUNT --------------------------------------------------

def _template(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"bench_templates_{name}",
        os.path.join(BENCH, "templates", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q6_BINDINGS = [{"DATE": f"{1993 + d % 5}-01-01", "DISCOUNT": f"0.0{d}",
                "QUANTITY": str(24 + d % 2)} for d in range(2, 10)]


@pytest.fixture(scope="module")
def q6_reference():
    """(sum, rows) of each binding from the benchmark's own NumPy
    reference data: integer predicates, so a row AT a bound is in."""
    q6 = _template("q6")
    import tpchdata
    sums = q6.reference(tpchdata, SF, Q6_BINDINGS)

    def part(li):
        out = []
        for b in Q6_BINDINGS:
            d, year = int(b["DISCOUNT"][2:]), int(b["DATE"][:4])
            lo = (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days
            hi = (datetime.date(year + 1, 1, 1)
                  - datetime.date(1970, 1, 1)).days
            out.append(int((
                (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
                & (li["l_discount_pct"] >= d - 1)
                & (li["l_discount_pct"] <= d + 1)
                & (li["l_quantity_int"] < int(b["QUANTITY"]))).sum()))
        return out
    counts = [sum(p[i] for p in tpchdata.map_lineitem(part, SF))
              for i in range(len(Q6_BINDINGS))]
    return q6, [(s[0][0], n) for s, n in zip(sums, counts)]


@pytest.mark.parametrize("template_cache", [False, True],
                         ids=["bound", "template"])
@pytest.mark.parametrize("which", range(8),
                         ids=[b["DISCOUNT"] for b in Q6_BINDINGS])
def test_q6_equals_the_reference(runner, q6_reference, which,
                                 template_cache):
    q6, answers = q6_reference
    want_sum, want_rows = answers[which]
    props = {"plan_template_cache": template_cache}
    sql = q6.SQL.format(**Q6_BINDINGS[which])
    c0 = REGISTRY.value("expr_device_constant_total")
    [(got,)] = runner.execute(sql, properties=props).rows
    assert abs(got - want_sum) <= 1e-12 * abs(want_sum)
    counted = sql.replace("sum(l_extendedprice * l_discount) as revenue",
                          "count(*)")
    assert runner.execute(counted, properties=props).rows == [(want_rows,)]
    assert want_rows > 0
    assert REGISTRY.value("expr_device_constant_total") == c0


# -- one resident copy of a scan, whatever the literals ------------------------

@pytest.mark.parametrize("name,first,second", [
    ("q6", {"DATE": "1994-01-01", "DISCOUNT": "0.06", "QUANTITY": "24"},
     {"DATE": "1996-01-01", "DISCOUNT": "0.03", "QUANTITY": "25"}),
    ("q1", {"DELTA": "90"}, {"DELTA": "61"}),
])
def test_two_bindings_share_one_scan_cache_entry(name, first, second):
    t = _template(name)
    catalogs = CatalogManager()
    conn = TpchConnector(sf=SF)
    catalogs.register("tpch", conn)
    r = LocalRunner(catalogs=catalogs, catalog="tpch")
    CACHE.clear()
    splits = len(conn.split_manager.splits(
        TableHandle("tpch", "default", "lineitem"), 8))
    plans = [r.plan(t.SQL.format(**b)) for b in (first, second)]
    pushdowns = {next(_nodes(p.root, TableScanNode)).pushdown
                 for p in plans}
    assert len(pushdowns) == 2          # the literal bounds do differ
    r.execute(t.SQL.format(**first))
    entries = len(CACHE)
    assert 1 <= entries <= splits
    m0 = REGISTRY.value("scan_cache_miss_total")
    r.execute(t.SQL.format(**second))
    assert len(CACHE) == entries        # ONE entry a split and column set
    assert REGISTRY.value("scan_cache_miss_total") == m0
    CACHE.clear()


def test_a_connector_that_applies_the_pushdown_keeps_a_key_a_bound(
        tmp_path):
    from presto_tpu.connectors.parquet import ParquetConnector
    from presto_tpu.formats.parquet import write_parquet
    write_parquet(str(tmp_path / "t.parquet"), Schema([("k", T.BIGINT)]),
                  [list(range(100))])
    catalogs = CatalogManager()
    conn = ParquetConnector(str(tmp_path))
    assert conn.applies_pushdown and not TpchConnector.applies_pushdown
    catalogs.register("pq", conn)
    r = LocalRunner(catalogs=catalogs, catalog="pq")
    CACHE.clear()
    assert r.execute("select count(*) from t where k < 10 + 10").rows \
        == [(20,)]
    one = len(CACHE)
    assert one >= 1
    assert r.execute("select count(*) from t where k < 10 + 20").rows \
        == [(30,)]
    assert len(CACHE) == 2 * one
    # the same bound again, spelled otherwise: its entry is there
    assert r.execute("select count(*) from t where k < 5 * 4").rows \
        == [(20,)]
    assert len(CACHE) == 2 * one
    CACHE.clear()
