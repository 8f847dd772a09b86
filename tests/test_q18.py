"""TPC-H Q18 at SF0.01 on the CPU: the engine against the plain NumPy
reference (``tpch_reference.q18_numpy``) row for row, with batches
small enough that the grouped state merges many times; where the
planner puts the IN-subquery's semi join; how many programs the state
compiles; and NOT IN and a nullable key against SQLite."""
import datetime
import sqlite3

import numpy as np
import pytest

import tpch_reference as R
from presto_tpu.connectors.spi import CatalogManager
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.planner.plan import (
    AggregationNode, JoinNode, SemiJoinNode, TableScanNode)

from tpch_queries import Q as TPCH_QUERIES

SF = 0.01
#: 60,175 lines in batches of 4,096: fifteen partials of the subquery's
#: group-by, so its state merges at four capacities and folds
ROWS_PER_BATCH = 4096

Q18 = next(q[1] for q in TPCH_QUERIES if q[0] == "q18")
assert "> 150" in Q18


def sql_for(quantity) -> str:
    return Q18.replace("> 150", f"> {quantity}")


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(sf=SF)


@pytest.fixture(scope="module")
def runner(connector):
    catalogs = CatalogManager()
    catalogs.register("tpch", connector)
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=ROWS_PER_BATCH)


@pytest.fixture(scope="module")
def host(connector):
    """The three tables' Q18 columns on the host, generated once."""
    return tuple(R.stage_host(connector, t, cols)[0] for t, cols in (
        ("customer", R.Q18_CUSTOMER_COLS), ("orders", R.Q18_ORDERS_COLS),
        ("lineitem", R.Q18_LINEITEM_COLS)))


@pytest.fixture(scope="module")
def order_sums(host):
    """Every order's sum of quantities, descending."""
    lk = np.concatenate([h[0] for h in host[2]])
    qty = np.concatenate([h[1] for h in host[2]])
    return np.sort(np.bincount(lk, weights=qty))[::-1]


def engine_rows(runner, quantity):
    epoch = datetime.date(1970, 1, 1)
    return [(r[0], int(r[1]), int(r[2]), (r[3] - epoch).days, float(r[4]),
             float(r[5])) for r in runner.execute(sql_for(quantity)).rows]


@pytest.mark.parametrize("which", ["many", "few", "none", "equal"])
def test_engine_equals_the_numpy_reference(runner, host, order_sums, which):
    top = float(order_sums[0])
    # "equal": the parameter EQUALS the largest order's sum, so HAVING's
    # strict > leaves that order out, and the one below it in
    quantity = {"many": 200, "few": float(order_sums[5]),
                "none": top, "equal": float(order_sums[1])}[which]
    if which == "equal":
        assert order_sums[0] > order_sums[1]
    want = R.q18_numpy(*host, quantity)
    got = engine_rows(runner, quantity)
    assert got == want            # DOUBLEs too: both are exact
    n = int((order_sums > quantity).sum())
    assert len(want) == min(n, 100)
    assert {"many": n > 100, "few": n == 5, "none": n == 0,
            "equal": n == 1}[which]


def test_the_semi_join_is_planned_on_orders_under_both_joins(runner):
    before = REGISTRY.value("plan_semijoin_pushed_total")
    root = runner.plan(sql_for(150)).root
    assert REGISTRY.value("plan_semijoin_pushed_total") == before + 1
    path = []           # the nodes from the root down to the semi join

    def find(node, above):
        if isinstance(node, SemiJoinNode):
            path.extend(above + [node])
            return True
        return any(find(c, above + [node]) for c in node.children)
    assert find(root, [])
    semi = path[-1]
    scan = semi.source
    while not isinstance(scan, TableScanNode):
        (scan,) = scan.children
    assert scan.table.table == "orders"
    assert not any(isinstance(n, JoinNode) for n in _walk(semi.source))
    joins = [n for n in path if isinstance(n, JoinNode)]
    assert len(joins) == 2
    # lineitem probes what is left of orders; c_name comes last
    assert _tables(joins[1].left) == {"lineitem"}
    assert _tables(joins[0].right) == {"customer"}
    assert sum(isinstance(n, SemiJoinNode) for n in _walk(root)) == 1
    text = "\n".join(r[0] for r in runner.execute(
        "explain " + sql_for(150)).rows)
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if "SemiJoin[" in ln)
    assert "TableScan[tpch.default.orders]" in lines[at + 1]
    indent = len(lines[at]) - len(lines[at].lstrip())
    above = [ln for ln in lines[:at]
             if "Join[" in ln and len(ln) - len(ln.lstrip()) < indent]
    assert len(above) == 2


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _tables(node):
    return {n.table.table for n in _walk(node)
            if isinstance(n, TableScanNode)}


def test_the_state_compiles_one_merge_program_a_capacity(runner):
    """PERF.md section 3: whatever the number of batches, the grouped
    state's programs are one network merge a capacity the binary
    counter reaches, the slice that cuts a partial to its live groups,
    the pad of the final fold and the finish."""
    from presto_tpu.obs.profiler import EXECUTABLES

    def programs():
        names = {}
        for rec in EXECUTABLES.snapshot():
            if rec["name"].startswith("grouped_aggregate"):
                names.setdefault(rec["name"], set()).add(
                    str(rec["static_key"]))
        return names
    before = programs()
    merges0 = REGISTRY.value("agg_state_merges_total")
    partials0 = REGISTRY.value("agg_partials_total")
    groups0 = REGISTRY.value("agg_state_groups_total")
    runner.execute(sql_for(150))
    partials = REGISTRY.value("agg_partials_total") - partials0
    merges = REGISTRY.value("agg_state_merges_total") - merges0
    assert partials >= 15
    # every state but one of each group-by merges into another once
    assert 14 <= merges <= partials
    # the subquery's 15,000 orders, and the outer aggregation's states
    # where their merges outgrew the cut floor and were read back
    assert REGISTRY.value("agg_state_groups_total") - groups0 >= 15000
    names = {k: v - before.get(k, set()) for k, v in programs().items()}
    # the subquery's state: 15 partials of 4096 lanes (1024 orders each)
    # -> merges at 4096 (the cut floor), 8192, 16384 and the fold: one
    # program a capacity means one jit-cache entry (the capacity is a
    # shape, not a key), and a handful of shapes
    assert len(names["grouped_aggregate_merge"]) <= 2
    assert len(names["grouped_aggregate_finish"]) <= 3
    assert "grouped_aggregate_pair" not in names or \
        len(names["grouped_aggregate_pair"]) <= 2


@pytest.fixture(scope="module")
def small():
    """A runner and a SQLite oracle over two small tables with NULL
    keys on both sides of an IN."""
    r = LocalRunner(tpch_sf=0.001)
    conn = sqlite3.connect(":memory:")
    tables = {
        "o": ("k bigint, c bigint, p double",
              [(1, 10, 5.0), (2, 10, 6.0), (3, 20, 7.0), (None, 20, 8.0),
               (5, 30, 9.0), (6, None, 1.0)]),
        "l": ("k bigint, q double",
              [(1, 3.0), (1, 4.0), (2, 1.0), (3, 9.0), (None, 50.0),
               (5, 2.0), (5, 2.5), (7, 100.0)]),
        "cu": ("c bigint, n varchar",
               [(10, "ten"), (20, "twenty"), (30, "thirty")]),
    }
    for t, (cols, rows) in tables.items():
        names = [c.split()[0] for c in cols.split(", ")]
        types = [c.split()[1] for c in cols.split(", ")]
        conn.execute(f"create table {t} ({', '.join(names)})")
        conn.executemany(
            f"insert into {t} values ({', '.join('?' * len(names))})", rows)
        values = ", ".join(
            "(" + ", ".join(
                f"cast(null as {ty})" if v is None else
                f"cast({v!r} as {ty})" for v, ty in zip(row, types)) + ")"
            for row in rows)
        r.execute(f"create table memory.default.{t} as select * from "
                  f"(values {values}) t({', '.join(names)})")
    return r, conn


@pytest.mark.parametrize("negated", ["in", "not in"])
@pytest.mark.parametrize("subquery", [
    "select k from {l} group by k having sum(q) > 4",     # a NULL key in it
    "select k from {l} where k is not null group by k having sum(q) > 4",
    "select k from {l} where q > 1000",                   # empty
])
def test_in_and_not_in_keep_their_answers_under_the_joins(
        small, negated, subquery):
    r, conn = small
    sql = ("select cu.n, o.k, o.p, sum(l.q) from {cu} cu, {o} o, {l} l "
           "where o.k {neg} ({sub}) and cu.c = o.c and o.k = l.k "
           "group by cu.n, o.k, o.p order by o.k")

    def text(prefix):
        names = {t: prefix + t for t in ("cu", "o", "l")}
        return sql.format(neg=negated, sub=subquery.format(**names), **names)
    plan = r.plan(text("memory.default."))
    semi = next(n for n in _walk(plan.root) if isinstance(n, SemiJoinNode))
    assert _tables(semi.source) == {"o"}      # planned on the one relation
    assert semi.negated == (negated == "not in")
    got = [tuple(row) for row in r.execute(text("memory.default.")).rows]
    want = conn.execute(text("")).fetchall()
    assert got == [tuple(w) for w in want]


def test_a_selective_inner_probe_is_cut_to_its_matches_first():
    """Batches of 2^18 lanes (the compactor looks at none under 2^17):
    an inner probe of a unique build that mostly misses is cut to its
    matches before the payload is gathered, one compaction a batch, and
    answers as the same join over small batches does."""
    sql = ("select o_orderkey, o_orderdate, l_linenumber, l_quantity "
           "from orders, lineitem where o_orderkey = l_orderkey "
           "and o_totalprice > 495000 order by o_orderkey, l_linenumber")
    want = LocalRunner(tpch_sf=0.05, rows_per_batch=8192).execute(sql).rows
    assert 1000 < len(want) < 300000 // 50
    wide = LocalRunner(tpch_sf=0.05, rows_per_batch=1 << 18)
    applied = REGISTRY.value("compact_applied_total")
    lanes_in = REGISTRY.value("compact_lanes_in_total")
    lanes_out = REGISTRY.value("compact_lanes_out_total")
    assert wide.execute(sql).rows == want
    # lineitem's 300K lines at SF0.05: two batches, each cut
    assert REGISTRY.value("compact_applied_total") - applied >= 2
    assert (REGISTRY.value("compact_lanes_out_total") - lanes_out) * 16 \
        <= REGISTRY.value("compact_lanes_in_total") - lanes_in
