"""TPC-H Q21 at SF0.01 on the CPU, and the residual semi join it rests
on: the engine against the benchmark's plain reference
(``benchmarks/templates/q21.py``, EXISTS and NOT EXISTS stated pair by
pair) and against SQLite; the `keyed` form of a residual semi join (one
lookup into a summary by key) against the `expand` form (every pair of
matching rows) and SQLite, comparison by comparison; what EXPLAIN
ANALYZE prints and what the counters count."""
import importlib.util
import os
import random
import sqlite3
import sys

import pytest

from presto_tpu.connectors.spi import CatalogManager, TableHandle
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.planner import optimizer
from presto_tpu.planner.plan import AggregationNode, SemiJoinNode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

SF = 0.01
#: 60,472 lines in batches of 8,192: eight partials a summary, so its
#: state merges at several capacities
ROWS_PER_BATCH = 8192

#: what Q21 and the tests below read of each table
COLUMNS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate",
                 "l_extendedprice"],
    "orders": ["o_orderkey", "o_orderstatus", "o_totalprice"],
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """(the template, its data module): the benchmark's own files, found
    as the harness finds them."""
    sys.path.insert(0, BENCH)
    try:
        data = _load("tpchdata_q21", os.path.join(BENCH, "tpchdata_q21.py"))
        sys.modules["tpchdata_q21"] = data
        return _load("bench_templates_q21",
                     os.path.join(BENCH, "templates", "q21.py")), data
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def runner():
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=SF))
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=ROWS_PER_BATCH)


@pytest.fixture(scope="module")
def oracle(runner):
    """SQLite over the same generated rows, the columns above; dates as
    ISO strings, which compare as dates do."""
    conn = sqlite3.connect(":memory:")
    tpch = runner.session.catalogs.get("tpch")
    for t, cols in COLUMNS.items():
        conn.execute(f"create table {t} ({', '.join(cols)})")
        th = TableHandle("tpch", "default", t)
        for split in tpch.split_manager.splits(th, 1):
            for b in tpch.page_source(split, cols).batches():
                conn.executemany(
                    f"insert into {t} values ({', '.join('?' * len(cols))})",
                    [tuple(v.isoformat() if hasattr(v, "isoformat")
                           else v.item() if hasattr(v, "item") else v
                           for v in r) for r in b.to_pylist()])
    conn.execute("create index l_ok on lineitem (l_orderkey)")
    conn.execute("create index o_ok on orders (o_orderkey)")
    conn.commit()
    return conn


def _delta(names, before):
    return {n: REGISTRY.value(n) - before[n] for n in names}


COUNTERS = ("semi_join_residual_total.keyed",
            "semi_join_residual_total.expand",
            "semi_join_expanded_lanes_total")


def _counters():
    return {n: REGISTRY.value(n) for n in COUNTERS}


# -- Q21 itself ---------------------------------------------------------------

NATIONS = random.Random(35).sample(range(25), 3)


@pytest.mark.parametrize("which", NATIONS)
def test_q21_equals_the_reference_and_sqlite(runner, oracle, bench, which):
    template, data = bench
    assert template.NATIONS == data.NATIONS and len(data.NATIONS) == 25
    binding = {"NATION": data.NATIONS[which]}
    sql = template.SQL.format(**binding)
    before = _counters()
    got = [(r[0], int(r[1])) for r in runner.execute(sql).rows]
    moved = _delta(COUNTERS, before)
    (want,) = template.reference(data, SF, [binding])
    assert got == want
    assert got == [tuple(r) for r in oracle.execute(sql).fetchall()]
    assert len(got) <= template.LIMIT and got
    # both subqueries read a summary by key; nothing is expanded
    assert moved == {"semi_join_residual_total.keyed": 2,
                     "semi_join_residual_total.expand": 0,
                     "semi_join_expanded_lanes_total": 0}


def test_the_reference_compares_every_pair_of_an_orders_lines(bench):
    """The reference by a third statement of the same sentence: a
    Python loop over three hand-made orders."""
    import numpy as np
    template, data = bench

    class Three:
        """Orders 1..3: two suppliers one late; one supplier; two
        suppliers both late."""
        NATIONS = data.NATIONS

        @staticmethod
        def row_counts(sf):
            return {"supplier": 4, "orders": 3}

        @staticmethod
        def map_lineitem(fn, sf):
            return [fn({"l_orderkey": np.array([1, 1, 2, 2, 3, 3]),
                        "l_suppkey": np.array([1, 2, 3, 3, 1, 4]),
                        "l_commitdate": np.array([5, 5, 5, 5, 5, 5]),
                        "l_receiptdate": np.array([9, 1, 9, 9, 9, 9]),
                        "lines": np.array([2, 2, 2])}, 1, 4)]

        @staticmethod
        def orders(sf, lo, hi):
            return {"o_orderstatus": np.array([0, 0, 0])}

        @staticmethod
        def supplier(sf, lo, hi):
            return {"s_suppkey": np.arange(1, 5),
                    "s_nationkey": np.array([7, 7, 7, 7]),
                    "s_name": np.array(["a", "b", "c", "d"], dtype=object)}

        nation = staticmethod(data.nation)
    (got,) = template.reference(Three, SF, [{"NATION": data.NATIONS[7]}])
    # order 1: supplier 1 alone is late among two suppliers; order 2 has
    # no other supplier; order 3's other supplier is late too
    assert got == [("a", 1)]


def test_explain_analyze_prints_the_keyed_form_twice(runner, bench):
    template, data = bench
    sql = template.SQL.format(NATION=data.NATIONS[20])
    before = _counters()
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + sql).rows)
    moved = _delta(COUNTERS, before)
    semis = [ln for ln in text.splitlines() if "SemiJoin[" in ln]
    assert len(semis) == 2
    assert all("residual on a unique build" in ln
               and ", residual keyed]" in ln for ln in semis)
    assert sum("$semi_min:=min(" in ln and "$semi_max:=max(" in ln
               for ln in text.splitlines()) == 2
    assert moved["semi_join_residual_total.keyed"] == 2
    assert moved["semi_join_expanded_lanes_total"] == 0
    plan = runner.plan(sql)
    found = [n for n in _walk(plan.root) if isinstance(n, SemiJoinNode)]
    assert len(found) == 2 and all(
        n.filtering_unique and isinstance(n.filtering, AggregationNode)
        for n in found)


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


# -- the two forms of a residual semi join ------------------------------------

#: the probe: NULL expression, NULL key, keys the build lacks (8, 9)
P_ROWS = [(1, 5), (1, 7), (2, 5), (2, None), (3, 4), (4, 1), (5, 6),
          (6, 2), (None, 5), (8, 5), (9, None), (7, 3), (7, 9)]
#: the build: key 1 two values; 2 one row; 3 every row one value; 4 a
#: NULL among values; 5 NULLs only; 6 values on both sides of the
#: probe's; 7 many rows; a NULL key
B_ROWS = [(1, 5), (1, 8), (2, 5), (3, 4), (3, 4), (3, 4), (4, None),
          (4, 1), (4, 3), (5, None), (5, None), (6, 1), (6, 2), (6, 3),
          (7, 3), (7, 4), (7, 5), (7, 9), (None, 7)]
OPS = {"ne": "<>", "lt": "<", "le": "<=", "gt": ">", "ge": ">="}


@pytest.fixture(scope="module")
def small():
    r = LocalRunner(tpch_sf=0.001)
    conn = sqlite3.connect(":memory:")
    for t, rows in (("p", P_ROWS), ("b", B_ROWS)):
        conn.execute(f"create table {t} (k, x)")
        conn.executemany(f"insert into {t} values (?, ?)", rows)
        values = ", ".join(
            "(" + ", ".join("cast(null as bigint)" if v is None
                            else f"cast({v} as bigint)" for v in row) + ")"
            for row in rows)
        r.execute(f"create table memory.default.{t} as select * from "
                  f"(values {values}) t(k, x)")
    return r, conn


def _both_forms(runner, sql, monkeypatch):
    """(rows by the keyed form, rows by the expand form, counters moved
    by each): the same statement planned with and without the summary."""
    out = []
    for keyed in (True, False):
        if not keyed:
            monkeypatch.setattr(optimizer, "_summarize_semi_residuals",
                                lambda node: node)
        before = _counters()
        rows = [tuple(r) for r in runner.execute(
            sql, properties={"plan_cache": "false"}).rows]
        out.append((rows, _delta(COUNTERS, before)))
    return out


@pytest.mark.parametrize("build", ["all", "empty"])
@pytest.mark.parametrize("flipped", [False, True])
@pytest.mark.parametrize("negated", [False, True])
@pytest.mark.parametrize("op", sorted(OPS))
def test_the_keyed_form_answers_as_the_expand_form(
        small, monkeypatch, op, negated, flipped, build):
    r, conn = small
    cmp = (f"b.x {OPS[op]} p.x" if flipped else f"p.x {OPS[op]} b.x")
    sql = ("select p.k, p.x from {p} p where {neg} exists (select * from "
           "{b} b where b.k = p.k and {cmp}{empty}) order by 1, 2")

    def text(prefix):
        return sql.format(p=prefix + "p", b=prefix + "b", cmp=cmp,
                          neg="not" if negated else "",
                          empty=" and b.x > 1000" if build == "empty" else "")
    (keyed, k_moved), (expand, e_moved) = _both_forms(
        r, text("memory.default."), monkeypatch)
    want = sorted(conn.execute(text("")).fetchall(),
                  key=lambda t: tuple((v is not None, v) for v in t))
    assert sorted(keyed, key=lambda t: tuple(
        (v is not None, v) for v in t)) == want
    assert keyed == expand
    assert k_moved["semi_join_residual_total.keyed"] == 1
    assert k_moved["semi_join_residual_total.expand"] == 0
    assert k_moved["semi_join_expanded_lanes_total"] == 0
    assert e_moved["semi_join_residual_total.keyed"] == 0
    assert e_moved["semi_join_residual_total.expand"] == 1
    assert e_moved["semi_join_expanded_lanes_total"] > 0


def test_a_residual_that_does_not_reduce_is_expanded(small):
    """Two comparisons, or an expression of the build's: no summary by
    key decides them; the m:n form does, as one named program."""
    r, conn = small
    for cond in ("b.x <> p.x and b.x < p.x + 3", "b.x + 1 > p.x"):
        sql = ("select p.k, p.x from {p} p where exists (select * from {b} "
               f"b where b.k = p.k and {cond}) order by 1, 2")
        before = _counters()
        got = [tuple(x) for x in r.execute(
            sql.format(p="memory.default.p", b="memory.default.b")).rows]
        moved = _delta(COUNTERS, before)
        assert got == conn.execute(sql.format(p="p", b="b")).fetchall()
        assert moved["semi_join_residual_total.expand"] == 1
        assert moved["semi_join_residual_total.keyed"] == 0
        assert moved["semi_join_expanded_lanes_total"] > 0
    from presto_tpu.obs.profiler import EXECUTABLES
    names = {rec["name"] for rec in EXECUTABLES.snapshot()}
    assert any(n.startswith("expr_semi_expand_") for n in names)


def test_a_primary_key_build_decides_any_residual_keyed(runner, oracle):
    """The filtering side unique by its statistics (orders' primary
    key): whatever the residual, it is decided on the one order a line
    finds, DOUBLE payload and all."""
    sql = ("select count(*), sum(l_suppkey) from lineitem where {neg} "
           "exists (select * from orders where o_orderkey = l_orderkey "
           "and o_totalprice < l_extendedprice * 8)")
    for neg in ("", "not"):
        before = _counters()
        got = [tuple(x) for x in runner.execute(sql.format(neg=neg)).rows]
        moved = _delta(COUNTERS, before)
        assert got == oracle.execute(sql.format(neg=neg)).fetchall()
        assert got[0][0] > 1000
        assert moved["semi_join_residual_total.keyed"] == 1
        assert moved["semi_join_expanded_lanes_total"] == 0
    from presto_tpu.obs.profiler import EXECUTABLES
    assert any(rec["name"].startswith("expr_semi_keyed_")
               for rec in EXECUTABLES.snapshot())


# -- the data fact: lineitem is generated clustered by its order key ----------

SORT_PATH = {"dense_grouping": "false", "plan_cache": "false"}
BY_ORDER = "select l_orderkey, min(l_suppkey), max(l_suppkey), count(*) " \
    "from lineitem where l_receiptdate > l_commitdate group by l_orderkey"


def _runner(connector):
    """Lineitem's 60,472 lines in ONE batch: a state that merges nowhere
    (the merge network compiles for 20 s a capacity on a CPU)."""
    catalogs = CatalogManager()
    catalogs.register("tpch", connector)
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=1 << 16)


def test_a_table_stated_clustered_is_grouped_without_a_sort():
    """The deployment's fact (``clustered_by``) reaches the plan
    (``ordered input``), the partial of a group-by over the clustering
    key holds no sort, and the answer is the one the sorting program
    gives; dead lanes among the live ones (the filter's) are squeezed
    out, not sorted out."""
    import re
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.ops.aggregation import AggSpec
    from presto_tpu.ops.jitcache import _grouped
    stated = _runner(TpchConnector(
        sf=SF, clustered_by={"lineitem": ["l_orderkey"]}))
    runner = _runner(TpchConnector(sf=SF))
    text = "\n".join(r[0] for r in stated.execute(
        "explain " + BY_ORDER, properties=SORT_PATH).rows)
    assert "ordered input" in text
    assert "ordered input" not in "\n".join(r[0] for r in runner.execute(
        "explain " + BY_ORDER, properties=SORT_PATH).rows)
    # by another column, or above a join: no promise
    assert "ordered input" not in "\n".join(r[0] for r in stated.execute(
        "explain select l_suppkey, count(*) from lineitem group by 1",
        properties=SORT_PATH).rows)
    got = sorted(stated.execute(BY_ORDER, properties=SORT_PATH).rows)
    assert got == sorted(runner.execute(BY_ORDER, properties=SORT_PATH).rows)
    # ... and the one the dense scatter path gives, which shares none
    # of the sort path's reducers
    assert got == sorted(runner.execute(
        BY_ORDER, properties={"plan_cache": "false"}).rows)
    assert len(got) > 13000
    rows = Batch.from_pydict({"k": (T.BIGINT, [1, 2, 2]),
                              "v": (T.BIGINT, [5, 6, 7])})
    aggs = (AggSpec("min", 1, T.BIGINT, "lo"),)

    def sorts(*ordered):
        lowered = _grouped((0,), aggs, "partial", None, None, False,
                           *ordered).fn.lower(rows)
        return re.findall(r"stablehlo\.(\w+)", lowered.as_text()).count(
            "sort")
    assert sorts() == 1 and sorts(True) == 0


def test_a_table_not_clustered_as_stated_fails_the_query():
    """A statistic that lies fails the query, it does not misgroup: a
    connector that states an order its generator does not keep."""
    from presto_tpu.connectors import tpch
    from presto_tpu.errors import QueryError, STATS_BOUND_VIOLATION
    liar = TpchConnector(sf=SF)
    liar._metadata.clustered_by = {"lineitem": ("l_suppkey",)}
    r = _runner(liar)
    sql = "select l_suppkey, min(l_orderkey) from lineitem group by 1"
    assert "ordered input" in "\n".join(
        x[0] for x in r.execute("explain " + sql,
                                properties=SORT_PATH).rows)
    with pytest.raises(QueryError) as e:
        r.execute(sql, properties=SORT_PATH)
    assert e.value.code == STATS_BOUND_VIOLATION
    with pytest.raises(ValueError, match="not generated clustered"):
        tpch.TpchConnector(sf=SF, clustered_by={"lineitem": ["l_suppkey"]})
    with pytest.raises(ValueError, match="not generated clustered"):
        tpch.TpchConnector(sf=SF, tables=["orders"],
                           clustered_by={"lineitem": ["l_orderkey"]})


@pytest.mark.parametrize("layout", ["in order", "gaps", "shuffled"])
def test_min_and_max_of_runs_equal_numpys(layout):
    """The sort path's min and max (a scan within the runs, no scatter)
    against NumPy: runs of 1 to 300 rows, NULL values, a batch in key
    order, the same with dead lanes among the live ones (squeezed out
    through the compress network) and shuffled (sorted)."""
    import numpy as np
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Schema
    from presto_tpu.ops.aggregation import AggSpec, grouped_aggregate
    rng = np.random.default_rng(35)
    runs = rng.integers(1, 300, size=40)
    keys = np.repeat(np.arange(40) * 3 + 1, runs)
    vals = rng.integers(-10**12, 10**12, size=len(keys))
    valid = rng.random(len(keys)) > 0.2
    valid[keys == 4] = False                    # a key with NULLs only
    live = np.ones(len(keys), dtype=bool)
    if layout == "gaps":
        live = rng.random(len(keys)) > 0.4
    if layout == "shuffled":
        order = rng.permutation(len(keys))
        keys, vals, valid = keys[order], vals[order], valid[order]
    cap = 8192
    pad = cap - len(keys)

    def lanes(a, fill):
        return np.concatenate([a, np.full(pad, fill, dtype=a.dtype)])
    b = Batch.from_arrays(
        Schema([("k", T.BIGINT), ("v", T.BIGINT)]),
        [lanes(keys, 0), lanes(vals, 0)],
        [np.ones(cap, dtype=bool), lanes(valid, False)], num_rows=cap)
    b = Batch(b.schema, b.columns, b.row_mask & lanes(live, False))
    aggs = (AggSpec("min", 1, T.BIGINT, "lo"), AggSpec("max", 1, T.BIGINT, "hi"),
            AggSpec("count", 1, T.BIGINT, "n"))
    got = sorted(grouped_aggregate(b, [0], aggs, mode="single",
                                   allow_dense=False).to_pylist())
    want = []
    for k in np.unique(keys[live]):
        m = live & (keys == k) & valid
        want.append((int(k), int(vals[m].min()) if m.any() else None,
                     int(vals[m].max()) if m.any() else None, int(m.sum())))
    assert got == want
    flag: list = []
    ordered = grouped_aggregate(b, [0], aggs, mode="single",
                                allow_dense=False, order_violation=flag)
    # the promise of order holds but for the shuffled batch, which says so
    assert (int(flag[0]) != 0) == (layout == "shuffled")
    if layout != "shuffled":
        assert sorted(ordered.to_pylist()) == want
