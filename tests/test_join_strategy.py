"""Stats-driven join strategy selection + the Pallas probe kernel.

The direct-address paths (single-key measured, multi-key planner-keyed,
and the Pallas probe kernel over either) must be RESULT-IDENTICAL to
the sorted-lookup path for every key shape the planner can route to
them — NULL keys, negative keys, keys sitting exactly on their stats
bounds, out-of-domain probe keys, composite key tuples, duplicate
(expansion) builds — because the dispatch is a pure performance
decision. Bounds that LIE (a live build key outside the planner's
promise) must fail the query with STATS_BOUND_VIOLATION, never drop
matches (the dense-grouping contract applied to joins)."""
import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, Schema
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.ops import join as J
from presto_tpu.ops import pallas_join as PJ


def _metric(name: str) -> float:
    for m in REGISTRY.snapshot():
        if m["name"] == name:
            return float(m.get("value", 0.0))
    return 0.0


def _rows(batch):
    def key(t):
        return tuple((v is None, str(type(v)), v) for v in t)
    return sorted([tuple(r) for r in batch.to_pylist()], key=key)


def _with_nulls(b: Batch, col: int, null_rows) -> Batch:
    cols = list(b.columns)
    mask = np.ones(b.capacity, dtype=bool)
    mask[list(null_rows)] = False
    c = cols[col]
    cols[col] = Column(c.type, c.data,
                       c.validity & jnp.asarray(mask), c.dictionary)
    return Batch(b.schema, cols, b.row_mask)


def _build(keys1, keys2, vals):
    return Batch.from_pydict({
        "k1": (T.BIGINT, keys1), "k2": (T.BIGINT, keys2),
        "v": (T.BIGINT, vals)})


# ---------------------------------------------------------------------------
# kernel parity: keyed direct vs sorted
# ---------------------------------------------------------------------------

def test_direct_keyed_vs_sorted_parity_random():
    rng = np.random.default_rng(7)
    n, m = 300, 500
    b1 = rng.integers(-20, 20, n).tolist()
    b2 = rng.integers(5, 12, n).tolist()
    build = _build(b1, b2, list(range(n)))
    build = _with_nulls(build, 0, [3, 50])
    probe = Batch.from_pydict({
        "p1": (T.BIGINT, rng.integers(-25, 25, m).tolist()),
        "p2": (T.BIGINT, rng.integers(3, 14, m).tolist()),
        "x": (T.BIGINT, list(range(m)))})
    probe = _with_nulls(probe, 1, [0, 7, 100])
    bounds = ((-20, 19), (5, 11))
    los, sizes, K = J.direct_keyed_plan(bounds)
    keyed = J.prepare_direct_keyed(build, [0, 1], los, sizes, K)
    sortp = J.prepare_build(build, [0, 1])
    # duplicates exist -> expansion join; parity across both tables
    for jt in ("inner", "left"):
        a = J.expand_join(probe, build, [0, 1], [0, 1], [2], ["v"], jt,
                          8, prepared=keyed)
        c = J.expand_join(probe, build, [0, 1], [0, 1], [2], ["v"], jt,
                          8, prepared=sortp)
        assert _rows(a) == _rows(c), jt
    assert int(J.max_multiplicity(keyed)) == int(J.max_multiplicity(sortp))
    for neg in (False, True):
        ma = J.semi_join_mask(probe, build, [0, 1], [0, 1], neg, False,
                              prepared=keyed)
        mc = J.semi_join_mask(probe, build, [0, 1], [0, 1], neg, False,
                              prepared=sortp)
        assert bool(jnp.all(ma == mc)), neg


def test_direct_keyed_bound_edges_and_out_of_domain():
    """Keys exactly on lo/hi match; probe keys outside the promised
    domain (which provably cannot match an in-bounds build) miss."""
    build = Batch.from_pydict({
        "k": (T.BIGINT, [-5, 0, 7]), "v": (T.BIGINT, [1, 2, 3])})
    los, sizes, K = J.direct_keyed_plan(((-5, 7),))
    keyed = J.prepare_direct_keyed(build, [0], los, sizes, K)
    probe = Batch.from_pydict({
        "p": (T.BIGINT, [-5, 7, -6, 8, 0, None])})
    out = J.lookup_join(probe, build, [0], [0], [1], ["v"], "inner",
                        prepared=keyed)
    assert _rows(out) == [(-5, 1), (0, 2), (7, 3)]
    left = J.lookup_join(probe, build, [0], [0], [1], ["v"], "left",
                         prepared=keyed)
    assert len(_rows(left)) == 6


def test_direct_keyed_plan_gates():
    assert J.direct_keyed_plan(()) is None
    assert J.direct_keyed_plan((None,)) is None
    assert J.direct_keyed_plan(((5, 4),)) is None          # empty span
    big = 1 << 20
    assert J.direct_keyed_plan(((0, big), (0, big))) is None  # product
    plan = J.direct_keyed_plan(((0, 9), (0, 9)))
    assert plan == ((0, 0), (10, 10), 100)


# ---------------------------------------------------------------------------
# Pallas probe kernel parity (interpret mode on the CPU mesh)
# ---------------------------------------------------------------------------

@pytest.fixture
def force_pallas(monkeypatch):
    monkeypatch.setattr(PJ, "FORCE_PALLAS_PROBE", True)


def test_pallas_lookup_parity_dtypes(force_pallas):
    """Row-exact against the XLA path across the payload dtype zoo:
    64-bit ints, doubles (digit planes), 32-bit ints, bools, dictionary
    strings, decimal128 limb pairs."""
    import decimal
    n = 40
    rng = np.random.default_rng(3)
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, n + 1))),
        "big": (T.BIGINT, rng.integers(-2**52, 2**52, n).tolist()),
        "dbl": (T.DOUBLE, (rng.standard_normal(n) * 1e9).tolist()),
        "i": (T.INTEGER, rng.integers(-100, 100, n).tolist()),
        "b": (T.BOOLEAN, (rng.random(n) < 0.5).tolist()),
        "s": (T.VARCHAR, [f"s{i % 7}" for i in range(n)]),
        "dec": (T.decimal(30, 2),
                [decimal.Decimal(int(v)) * 1000000 +
                 decimal.Decimal(int(w)) / 100
                 for v, w in zip(rng.integers(-2**52, 2**52, n),
                                 rng.integers(0, 10**4, n))]),
    })
    build = _with_nulls(build, 1, [2, 5])
    build = _with_nulls(build, 6, [4])
    probe = Batch.from_pydict({
        "p": (T.BIGINT, rng.integers(-3, n + 4, 64).tolist())})
    prep = J.prepare_direct(build, [0], 1, 64)
    payload = [1, 2, 3, 4, 5, 6]
    names = ["big", "dbl", "i", "b", "s", "dec"]
    for jt in ("inner", "left"):
        a = PJ.lookup_join_direct(probe, build, [0], [0], payload,
                                  names, jt, prep)
        c = J.lookup_join(probe, build, [0], [0], payload, names, jt,
                          prepared=prep)
        assert _rows(a) == _rows(c), jt


def test_pallas_supports_join_gate():
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, 200))),
        "v": (T.BIGINT, list(range(199)))})
    sortp = J.prepare_build(build, [0])
    assert not PJ.supports_join(sortp, build, [1])   # not direct
    prep = J.prepare_direct(build, [0], 1, 256)
    assert not PJ.kernel_enabled()                   # CPU backend
    assert not PJ.supports_join(prep, build, [1])


def test_pallas_supports_join_gate_forced(force_pallas):
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, 200))),
        "v": (T.BIGINT, list(range(199)))})
    prep = J.prepare_direct(build, [0], 1, 256)
    assert PJ.supports_join(prep, build, [1])
    assert not PJ.supports_join(prep, build, list(range(32)))  # bits


def _star_runner(sf, rows_per_batch):
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=sf))
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=rows_per_batch)


def test_pallas_engine_parity(force_pallas):
    """The 3-way tpch star chain runs the fused pipeline through the
    kernel (interpret mode) with the session property on, and lands on
    the rows of the default XLA probe."""
    r = _star_runner(0.01, 1 << 14)
    q = ("select n_name, count(*) c from orders "
         "join customer on o_custkey = c_custkey "
         "join nation on c_nationkey = n_nationkey "
         "group by n_name order by n_name")
    before = _metric("join_strategy_selected_total.direct.replicated")
    on = r.execute(q, properties={"join_pallas_probe": True}).rows
    after = _metric("join_strategy_selected_total.direct.replicated")
    assert after > before
    off = r.execute(q).rows
    assert on == off


def test_pallas_probe_off_by_default():
    """The kernel does not lower for a TPU v5e (ops/pallas_join
    docstring, tests/test_tpu_compile.py): the property defaults off in
    the registry AND at the executor's read, even on a backend that
    reports the kernel enabled."""
    from presto_tpu.config import SESSION_PROPERTIES
    assert SESSION_PROPERTIES["join_pallas_probe"].default is False
    from presto_tpu.exec.local import _Executor
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.planner.planner import Session
    cats = CatalogManager()
    assert not _Executor(Session(cats), 1 << 13)._pallas_probe_on()
    on = Session(cats, properties={"join_pallas_probe": True})
    assert _Executor(on, 1 << 13)._pallas_probe_on()


@pytest.mark.parametrize("fused", [False, True])
def test_pallas_kernel_failure_fails_query(monkeypatch, fused):
    """With join_pallas_probe=true a kernel that fails to lower fails
    the query with the compiler's message — nothing re-runs it on
    another implementation. With the default (off) the same query
    never reaches the kernel."""
    monkeypatch.setattr(PJ, "kernel_enabled", lambda: True)

    def boom(*a, **k):
        raise NotImplementedError("Only 2D gather is supported")
    monkeypatch.setattr(PJ, "lookup_join_direct", boom)
    monkeypatch.setattr(
        "presto_tpu.exec.local.lookup_join_pallas_jit", boom)
    r = _star_runner(0.002, 1 << 13)
    q = ("select count(*) from orders join customer "
         "on o_custkey = c_custkey where c_nationkey = 3")
    props = {"fused_pipeline": fused}
    assert r.execute(q, properties=props).rows[0][0] > 0
    with pytest.raises(Exception, match="Only 2D gather"):
        r.execute(q, properties={**props, "join_pallas_probe": True})


# ---------------------------------------------------------------------------
# planner: strategy attaches from stats, flips when stats change
# ---------------------------------------------------------------------------

def _find(node, cls):
    from presto_tpu.planner.plan import PlanNode
    out = []

    def walk(n):
        if isinstance(n, cls):
            out.append(n)
        for c in n.children:
            walk(c)
    walk(node)
    return out


@pytest.fixture(scope="module")
def tpch_runner():
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=0.01))
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=1 << 14)


def test_planner_attaches_join_bounds(tpch_runner):
    from presto_tpu.planner.plan import JoinNode
    plan = tpch_runner.plan(
        "select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey")
    joins = _find(plan.root, JoinNode)
    assert joins and joins[0].key_bounds == ((0, 24),)
    ex = tpch_runner.execute(
        "explain select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey").rows
    text = "\n".join(r[0] for r in ex)
    assert "direct bounds=[0..24]" in text


def test_planner_bounds_flip_with_stats(tpch_runner, monkeypatch):
    """Same SQL, stats withdrawn -> the strategy flips to sorted (no
    key_bounds); join_dense_path=false pins the old behavior too."""
    from presto_tpu.connectors.spi import TableStats
    from presto_tpu.planner.plan import JoinNode
    sql = ("select c_name, n_name from customer "
           "join nation on c_nationkey = n_nationkey")
    conn = tpch_runner.session.catalogs.get("tpch")
    meta = conn.metadata
    real = meta.table_stats

    def no_bounds(table):
        st = real(table)
        if table.table == "nation":
            return TableStats(row_count=st.row_count, columns={},
                              primary_key=st.primary_key)
        return st
    monkeypatch.setattr(type(meta), "table_stats",
                        lambda self, t: no_bounds(t))
    try:
        plan = tpch_runner.plan(sql)
    finally:
        monkeypatch.undo()
    joins = _find(plan.root, JoinNode)
    assert joins and joins[0].key_bounds == ()
    # session escape hatch
    old = dict(tpch_runner.session.properties)
    tpch_runner.session.properties["join_dense_path"] = False
    try:
        plan2 = tpch_runner.plan(sql)
    finally:
        tpch_runner.session.properties.clear()
        tpch_runner.session.properties.update(old)
    assert _find(plan2.root, JoinNode)[0].key_bounds == ()


def test_semi_distribution_from_stats(tpch_runner):
    """Semi joins stop broadcasting membership everywhere: a filtering
    set estimated above broadcast_join_row_limit partitions; NULL-aware
    anti joins always replicate (global NULL semantics)."""
    from presto_tpu.planner.plan import SemiJoinNode
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer)")
    plan = tpch_runner.plan(sql)
    semis = _find(plan.root, SemiJoinNode)
    assert semis and semis[0].distribution == "replicated"
    old = dict(tpch_runner.session.properties)
    tpch_runner.session.properties["broadcast_join_row_limit"] = 100
    try:
        plan2 = tpch_runner.plan(sql)
        semis2 = _find(plan2.root, SemiJoinNode)
        assert semis2 and semis2[0].distribution == "partitioned"
        anti = tpch_runner.plan(
            "select count(*) from orders where o_custkey not in "
            "(select c_custkey from customer)")
        asemis = _find(anti.root, SemiJoinNode)
        assert asemis and asemis[0].negated
        assert asemis[0].distribution == "replicated"
    finally:
        tpch_runner.session.properties.clear()
        tpch_runner.session.properties.update(old)


def test_semi_partitioned_row_parity(tpch_runner):
    """Forcing the partitioned semi distribution returns the identical
    rows (the fragmenter/mesh path composes per-partition verdicts)."""
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer where c_nationkey < 5)")
    a = tpch_runner.execute(sql).rows
    b = tpch_runner.execute(
        sql, properties={"broadcast_join_row_limit": 10}).rows
    assert a == b


# ---------------------------------------------------------------------------
# bounds that lie -> STATS_BOUND_VIOLATION through the error channel
# ---------------------------------------------------------------------------

def test_join_bound_violation_fails_query():
    from presto_tpu.connectors.spi import (CatalogManager, ColumnStats,
                                           TableStats)
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.errors import QueryError
    from presto_tpu.exec.runner import LocalRunner
    conn = MemoryConnector()
    catalogs = CatalogManager()
    catalogs.register("memory", conn)
    r = LocalRunner(catalogs=catalogs, catalog="memory")
    r.execute("create table memory.default.dim as select * from "
              "(values (1, 'a'), (2, 'b'), (99, 'z')) t(k, name)")
    r.execute("create table memory.default.fact as select * from "
              "(values (1, 10), (2, 20), (99, 30)) t(fk, v)")

    lying = {
        "dim": TableStats(
            row_count=3.0,
            columns={"k": ColumnStats(3, 0.0, 1, 5)},  # 99 violates
            primary_key=("k",)),
        "fact": TableStats(row_count=3.0, columns={}),
    }
    meta = conn.metadata
    monkeypatch_stats = lambda self, t: lying.get(
        t.table, TableStats(row_count=3.0))
    orig = type(meta).table_stats
    type(meta).table_stats = monkeypatch_stats
    try:
        plan = r.plan("select v, name from memory.default.fact "
                      "join memory.default.dim on fk = k")
        from presto_tpu.planner.plan import JoinNode
        joins = _find(plan.root, JoinNode)
        assert joins and joins[0].key_bounds == ((1, 5),)
        with pytest.raises(QueryError) as ei:
            r.execute("select v, name from memory.default.fact "
                      "join memory.default.dim on fk = k")
        assert ei.value.name == "STATS_BOUND_VIOLATION"
        # honest bounds: same query with the real (empty) stats runs.
        # plan_cache=false: the cached plan still carries the lying
        # bounds (stats changes don't bump connector data versions)
        type(meta).table_stats = orig
        rows = r.execute("select v, name from memory.default.fact "
                         "join memory.default.dim on fk = k",
                         properties={"plan_cache": False}).rows
        assert sorted(rows) == [(10, 'a'), (20, 'b'), (30, 'z')]
    finally:
        type(meta).table_stats = orig


# ---------------------------------------------------------------------------
# observability: EXPLAIN ANALYZE strategy rows
# ---------------------------------------------------------------------------

def test_explain_analyze_shows_strategy(tpch_runner):
    ex = tpch_runner.execute(
        "explain analyze select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey").rows
    text = "\n".join(r[0] for r in ex)
    assert "[strategy direct/replicated]" in text
