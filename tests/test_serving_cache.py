"""ISSUE 13: parameter-generic plan templates + versioned result cache.

Covers the serving-cache stack end to end: template fingerprinting and
binding (one plan + one warm executable set across a fleet of
bindings), optimizer guards with per-binding fallback, the
result/subplan cache's hit / partial (append-only incremental
maintenance) / invalidation / veto semantics, admission-slot release on
the hit fast path, and the cross-session parse-cache regression.
"""

import pytest

from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.orc import OrcConnector
from presto_tpu.connectors.spi import CatalogManager
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY

TPROPS = {"plan_template_cache": True}
RPROPS = {"result_cache": True}


def _metric(name: str) -> float:
    for m in REGISTRY.snapshot():
        if m["name"] == name:
            return m["value"]
    return 0.0


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.01)


@pytest.fixture()
def file_runner(tmp_path):
    tmp = tmp_path / "orc"
    tmp.mkdir()
    cats = CatalogManager()
    cats.register("tpch", TpchConnector(sf=0.01))
    cats.register("memory", MemoryConnector())
    cats.register("orc", OrcConnector(str(tmp)))
    return LocalRunner(catalogs=cats, catalog="tpch")


# -- plan templates -----------------------------------------------------------

def test_template_parity_across_bindings(runner):
    """Row-exact parity: the same statement shape with different
    literals returns identical rows under the template cache, serving
    N bindings from ONE optimized plan."""
    sqls = ["select count(*), sum(l_extendedprice) from lineitem "
            f"where l_quantity > {q}" for q in (10, 20, 30)]
    cold = [runner.execute(s).rows for s in sqls]
    h0, m0 = (_metric("plan_template_cache_hit_total"),
              _metric("plan_template_cache_miss_total"))
    warm = [runner.execute(s, properties=TPROPS).rows for s in sqls]
    assert warm == cold
    assert _metric("plan_template_cache_miss_total") - m0 == 1
    assert _metric("plan_template_cache_hit_total") - h0 == 2


def test_template_shares_compiled_kernels(runner):
    """The whole point: a new binding re-dispatches the SAME traced
    executable — the expression compile cache must not grow."""
    from presto_tpu.expr.compiler import _DEFAULT
    sql = ("select l_returnflag, count(*) from lineitem "
           "where l_discount between 0.0%d and 0.08 "
           "group by l_returnflag order by l_returnflag")
    cold = [runner.execute(sql % d).rows for d in (1, 2, 3)]
    runner.execute(sql % 1, properties=TPROPS)       # template build
    before = len(_DEFAULT._cache)
    warm = [runner.execute(sql % d, properties=TPROPS).rows
            for d in (1, 2, 3)]
    assert warm == cold
    assert len(_DEFAULT._cache) == before


def test_execute_fleet_parity(runner):
    """EXECUTE with different bindings rides one template."""
    runner.execute("prepare fleet_q from select count(*) from lineitem "
                   "where l_quantity > ?")
    h0 = _metric("plan_template_cache_hit_total")
    got = [runner.execute(f"execute fleet_q using {q}",
                          properties=TPROPS).rows for q in (5, 15, 25)]
    want = [runner.execute(
        f"select count(*) from lineitem where l_quantity > {q}").rows
        for q in (5, 15, 25)]
    assert got == want
    assert _metric("plan_template_cache_hit_total") - h0 >= 2


def test_guard_fallback_on_flipped_pushdown_literal(runner):
    """A DATE range literal feeds scan-pushdown bounds: the template
    records an equality guard, a binding that flips it falls back to
    the per-binding fingerprint — with correct rows either way."""
    s1 = ("select count(*) from lineitem "
          "where l_shipdate <= date '1998-09-02'")
    s2 = ("select count(*) from lineitem "
          "where l_shipdate <= date '1997-09-02'")
    c1, c2 = runner.execute(s1).rows, runner.execute(s2).rows
    assert runner.execute(s1, properties=TPROPS).rows == c1
    g0 = _metric("plan_template_cache_guard_fallback_total")
    # same binding again: guard holds, template serves
    assert runner.execute(s1, properties=TPROPS).rows == c1
    assert _metric("plan_template_cache_guard_fallback_total") == g0
    # flipped binding: guard miss -> per-binding fallback, right rows
    assert runner.execute(s2, properties=TPROPS).rows == c2
    assert _metric("plan_template_cache_guard_fallback_total") == g0 + 1


def test_template_plans_keep_pushdown_quality(runner):
    """The guarded consult keeps literal-derived scan pushdown on the
    template plan (the bound would vanish if Params were opaque to
    the pushdown extractor)."""
    from presto_tpu.serving.template import parameterize
    from presto_tpu.serving.plancache import parse_cached
    from presto_tpu.planner.optimizer import optimize
    from presto_tpu.planner.planner import plan_query
    from presto_tpu.planner.plan import TableScanNode
    stmt = parse_cached("select count(*) from lineitem "
                        "where l_shipdate <= date '1998-09-02'")
    _t, marked, values = parameterize(stmt)
    assert values                      # the date hole-punched
    plan = optimize(plan_query(marked, runner.session), runner.session)

    def scans(n):
        if isinstance(n, TableScanNode):
            yield n
        for c in n.children:
            yield from scans(c)
    [scan] = list(scans(plan.root))
    assert any(name == "l_shipdate" and hi is not None
               for name, _lo, hi in scan.pushdown)


def test_template_mix_of_kinds(runner):
    """BIGINT / DOUBLE / short-DECIMAL / DATE literals parameterize;
    kind is part of the key so 5 and 5.0 never share a template."""
    from presto_tpu.serving.template import parameterize
    from presto_tpu.serving.plancache import parse_cached
    a = parameterize(parse_cached(
        "select 1 from lineitem where l_quantity > 5"))
    b = parameterize(parse_cached(
        "select 1 from lineitem where l_quantity > 5.0"))
    assert a[0] != b[0]                # different template ASTs
    assert a[2] == {0: 5} and b[2] == {0: 5.0}
    # LIMIT counts and GROUP BY ordinals never hole-punch
    t, _m, v = parameterize(parse_cached(
        "select l_returnflag, count(*) from lineitem "
        "group by 1 order by 1 limit 3"))
    assert v == {}


def test_parse_cache_does_not_leak_across_sessions():
    """ISSUE 13 satellite: parse_cached keys on TEXT only; resolution
    happens at plan time, so two sessions with different default
    catalog/schema share the parsed AST but NOT the plan — the
    fingerprint (which folds catalog/schema/connector identities in)
    is what separates them."""
    from presto_tpu.serving.plancache import PlanCache, parse_cached
    from presto_tpu.batch import Batch, Schema
    from presto_tpu import types as T

    def mem_runner(vals):
        cats = CatalogManager()
        mem = MemoryConnector()
        cats.register("memory", mem)
        cats.register("tpch", TpchConnector(sf=0.001))
        r = LocalRunner(catalogs=cats, catalog="memory")
        schema = Schema([("x", T.BIGINT)])
        mem.create_table("t", schema)
        mem.append("t", Batch.from_pydict({"x": (T.BIGINT, vals)}))
        return r

    r1, r2 = mem_runner([1, 2, 3]), mem_runner([10, 20])
    sql = "select sum(x) s from t"
    # one parsed AST object serves both sessions
    assert parse_cached(sql) is parse_cached(sql)
    stmt = parse_cached(sql)
    assert PlanCache.fingerprint(stmt, r1.session) != \
        PlanCache.fingerprint(stmt, r2.session)
    # and (with every cache enabled) each session sees its own table
    props = {**TPROPS, **RPROPS}
    assert r1.execute(sql, properties=props).rows == [(6,)]
    assert r2.execute(sql, properties=props).rows == [(30,)]
    assert r1.execute(sql, properties=props).rows == [(6,)]


# -- result cache -------------------------------------------------------------

def test_result_cache_hit_and_write_invalidation(file_runner, tmp_path):
    """Eager invalidation rides spi.notify_data_change for memory,
    sqlite and filebase writes — the same path the plan cache uses."""
    from presto_tpu.connectors.sqlite import SqliteConnector
    r = file_runner
    r.session.catalogs.register(
        "sqlite", SqliteConnector(str(tmp_path / "db.sqlite")))
    cases = [
        ("memory", "select count(*) c, sum(q) s from memory.t"),
        ("sqlite", "select count(*) c, sum(q) s from sqlite.t"),
        ("orc", "select count(*) c, sum(q) s from orc.t"),
    ]
    for cat, _ in cases:
        r.execute(f"create table {cat}.t as select l_orderkey k, "
                  "l_quantity q from lineitem where l_orderkey < 100")
    for cat, sql in cases:
        h0 = _metric("result_cache_hit_total")
        a = r.execute(sql, properties=RPROPS).rows
        b = r.execute(sql, properties=RPROPS).rows
        assert a == b
        assert _metric("result_cache_hit_total") == h0 + 1
        i0 = _metric("result_cache_invalidated_total")
        r.execute(f"insert into {cat}.t select l_orderkey k, "
                  "l_quantity q from lineitem "
                  "where l_orderkey between 100 and 150")
        if cat != "orc":
            # filebase appends stay resident for incremental
            # maintenance; the others must drop eagerly, BEFORE the
            # next lookup
            assert _metric("result_cache_invalidated_total") > i0
        c = r.execute(sql, properties=RPROPS).rows
        assert c == r.execute(sql).rows
        assert c != a                  # the write is visible


def test_result_cache_mid_execution_write_vetoes_insert(file_runner):
    """The write-epoch TOCTOU contract: a connector write notifying
    while the query runs must veto the insert (the stored rows could
    straddle versions)."""
    from presto_tpu.connectors import spi
    r = file_runner
    r.execute("create table memory.v as select l_orderkey k from "
              "lineitem where l_orderkey < 50")
    mem = r.session.catalogs.get("memory")
    sql = "select count(*) from memory.v"

    fired = []
    orig = MemoryConnector.page_source

    def chaotic(self, split, columns, pushdown=None,
                rows_per_batch=1 << 17):
        if not fired:
            fired.append(1)
            spi.notify_data_change(mem, "unrelated")  # mid-run write
        return orig(self, split, columns, pushdown, rows_per_batch)

    MemoryConnector.page_source = chaotic
    try:
        m0 = _metric("result_cache_miss_total")
        r.execute(sql, properties=RPROPS)
        # vetoed: the very next execution is a miss again
        r.execute(sql, properties=RPROPS)
        assert _metric("result_cache_miss_total") == m0 + 2
    finally:
        MemoryConnector.page_source = orig
    # clean run now inserts and hits
    r.execute(sql, properties=RPROPS)
    h0 = _metric("result_cache_hit_total")
    r.execute(sql, properties=RPROPS)
    assert _metric("result_cache_hit_total") == h0 + 1


def test_result_cache_epoch_api_veto():
    from presto_tpu.serving.resultcache import RESULTS
    from presto_tpu.exec.local import QueryResult
    epoch = RESULTS.epoch()
    RESULTS.note_write()
    ok = RESULTS.put(b"k-veto", QueryResult(["a"], [], [(1,)]),
                     deps=[], epoch=epoch)
    assert not ok


def test_incremental_partial_maintenance(file_runner):
    """Append-only filebase growth: only the changed splits recompute;
    the merged result is row-exact vs a cold run, for grouped AND
    global distributive aggregations; rewrites fall back to a miss."""
    r = file_runner
    r.execute("create table orc.inc as select l_orderkey k, "
              "l_quantity q, l_returnflag flag from lineitem "
              "where l_orderkey < 500")
    grouped = ("select flag, count(*) c, sum(q) sq, max(k) mk "
               "from orc.inc group by flag order by flag")
    glob = "select count(*), sum(q), min(k) from orc.inc where q > 10"
    r.execute(grouped, properties=RPROPS)
    r.execute(glob, properties=RPROPS)
    p0 = _metric("result_cache_partial_total")
    r.execute("insert into orc.inc select l_orderkey k, l_quantity q, "
              "l_returnflag flag from lineitem "
              "where l_orderkey between 500 and 1000")
    assert r.execute(grouped, properties=RPROPS).rows == \
        r.execute(grouped).rows
    assert r.execute(glob, properties=RPROPS).rows == \
        r.execute(glob).rows
    assert _metric("result_cache_partial_total") == p0 + 2
    # the re-stamped entry serves plain hits afterwards
    h0 = _metric("result_cache_hit_total")
    r.execute(grouped, properties=RPROPS)
    assert _metric("result_cache_hit_total") == h0 + 1
    # rewrite (drop + recreate): old files gone -> full miss, not merge
    r.execute("drop table orc.inc")
    r.execute("create table orc.inc as select l_orderkey k, "
              "l_quantity q, l_returnflag flag from lineitem "
              "where l_orderkey < 300")
    p1 = _metric("result_cache_partial_total")
    assert r.execute(grouped, properties=RPROPS).rows == \
        r.execute(grouped).rows
    assert _metric("result_cache_partial_total") == p1


def test_concurrent_partial_hits_never_double_apply(file_runner):
    """Two lookups racing on the same appended entry each merge the
    delta into the LOOKUP-TIME snapshot; the second re-stamp is
    rejected (base_deps compare), so the delta can never double-count
    — the 100-client repeated-mix race."""
    from presto_tpu.serving import resultcache as RC
    from presto_tpu.serving.plancache import bound_fingerprint, \
        parse_cached
    r = file_runner
    r.execute("create table orc.race as select l_orderkey k, "
              "l_quantity q from lineitem where l_orderkey < 400")
    sql = "select count(*) c, sum(q) sq from orc.race"
    r.execute(sql, properties=RPROPS)           # insert entry
    r.execute("insert into orc.race select l_orderkey k, l_quantity q "
              "from lineitem where l_orderkey between 400 and 800")
    stmt = parse_cached(sql)
    import dataclasses as dc
    session = dc.replace(r.session, properties={**r.session.properties,
                                                **RPROPS})
    key = bound_fingerprint(stmt, session)
    out1, ph1 = RC.RESULTS.get(key)
    out2, ph2 = RC.RESULTS.get(key)
    assert out1 == out2 == "partial"
    # first racer completes normally
    restrict = RC.split_predicate(session, ph1.spec, ph1.new_files)
    d1 = RC.subplan_result(ph1.plan, ph1.spec, session, 1 << 17,
                           split_restrict=restrict)
    m1 = RC.merge_subplan_rows(ph1.spec, ph1.base_subplan, d1)
    o1 = RC.replay_suffix(ph1.plan, ph1.spec, m1, session, 1 << 17)
    assert RC.RESULTS.update(ph1, o1, m1)
    # second racer merged against ITS OWN snapshot: identical rows,
    # and its re-stamp is rejected
    m2 = RC.merge_subplan_rows(ph2.spec, ph2.base_subplan, d1)
    o2 = RC.replay_suffix(ph2.plan, ph2.spec, m2, session, 1 << 17)
    assert sorted(o2.rows) == sorted(o1.rows)
    assert not RC.RESULTS.update(ph2, o2, m2)
    # and the surviving entry matches a cold run
    assert r.execute(sql, properties=RPROPS).rows == \
        r.execute(sql).rows


def test_result_cache_stores_materialized_plans(file_runner):
    """With templates + result cache combined, the CACHED plan must be
    binding-free: a later query for the same bound key can take the
    template guard-fallback path (no binding scope), and the partial
    delta/suffix replay re-executes the stored plan there."""
    import dataclasses as dc
    from presto_tpu.expr.params import has_params
    from presto_tpu.serving import resultcache as RC
    from presto_tpu.serving.plancache import bound_fingerprint, \
        parse_cached
    r = file_runner
    r.execute("create table orc.mat as select l_orderkey k, "
              "l_quantity q from lineitem where l_orderkey < 200")
    props = {**TPROPS, **RPROPS}
    sql = "select count(*) c, sum(q) s from orc.mat where q > 5"
    r.execute(sql, properties=props)
    session = dc.replace(r.session,
                         properties={**r.session.properties, **props})
    key = bound_fingerprint(parse_cached(sql), session)
    outcome, entry = RC.RESULTS.get(key)
    assert outcome == "hit"
    assert entry.spec is not None          # incremental-eligible
    assert not has_params(entry.plan)      # materialized for replay


def test_result_cache_eviction_under_limit(file_runner):
    from presto_tpu.serving.resultcache import RESULTS
    r = file_runner
    r.execute("create table memory.ev as select l_orderkey k from "
              "lineitem where l_orderkey < 200")
    old_limit = RESULTS.pool.limit
    try:
        # order-robust: the cache is process-global, so size the limit
        # from a MEASURED entry footprint instead of a fixed byte count
        # (a fixed 8 KiB fails in isolation where 5 small entries fit,
        # and put() silently rejects any entry larger than the limit)
        RESULTS.clear()
        r.execute("select count(*) from memory.ev where k > 0",
                  properties=RPROPS)
        size0 = RESULTS.pool.reserved
        assert size0 > 0
        limit = int(size0 * 2.5)        # room for 2 entries, never 3
        RESULTS.set_limit(limit)
        e0 = _metric("result_cache_evicted_total")
        for lo in (50, 100, 150):
            r.execute(f"select count(*) from memory.ev where k > {lo}",
                      properties=RPROPS)
        assert RESULTS.pool.reserved <= limit
        assert _metric("result_cache_evicted_total") > e0
        assert len(RESULTS) <= 2
    finally:
        RESULTS.set_limit(old_limit)


def test_explain_analyze_result_cache_line(file_runner):
    r = file_runner
    r.execute("create table memory.t as select l_orderkey k from "
              "lineitem where l_orderkey < 100")
    sql = "select count(*) from memory.t"
    r.execute(sql, properties=RPROPS)
    out = r.execute("explain analyze " + sql, properties=RPROPS)
    text = "\n".join(row[0] for row in out.rows)
    assert "Result cache:" in text
    assert "cached" in text


def test_result_cache_hit_releases_admission_slot_and_ctx():
    """ISSUE 13 satellite: the result-cache-hit fast path must release
    the resource-group slot AND the serving context (group memory back
    to zero) exactly like a cold run — extends PR 8's leak test."""
    from presto_tpu.server.protocol import PrestoTpuServer
    srv = PrestoTpuServer(
        LocalRunner(tpch_sf=0.001),
        resource_groups={
            "rootGroups": [{"name": "g", "hardConcurrencyLimit": 2,
                            "softMemoryLimit": 1 << 30}],
            "selectors": [{"group": "g"}]})
    try:
        srv.runner.session.properties["result_cache"] = True
        h0 = _metric("result_cache_hit_total")
        for _ in range(2):
            q = srv.create_query(
                "select count(*) from lineitem", {})
            assert q.done.wait(timeout=30)
            assert q.state == "FINISHED"
        assert _metric("result_cache_hit_total") == h0 + 1
        info = srv.resource_groups.info()[0]
        assert info["numRunning"] == 0 and info["numQueued"] == 0
        assert info["memoryReservedBytes"] == 0
    finally:
        srv.stop()


def test_cluster_template_and_result_cache_parity():
    """Row-exact parity on the ClusterRunner path: template-cached
    plans materialize bindings before fragmenting, result-cache hits
    serve stored rows, and a connector write invalidates them."""
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.server.worker import WorkerServer
    workers = [WorkerServer(tpch_sf=0.001) for _ in range(2)]
    for w in workers:
        w.start()
    urls = [f"http://127.0.0.1:{w.port}" for w in workers]
    runner = ClusterRunner(urls, tpch_sf=0.001, heartbeat=False)
    try:
        props = {**TPROPS, **RPROPS}
        sql = ("select n_regionkey, count(*) c from nation "
               "where n_nationkey > %d group by n_regionkey "
               "order by n_regionkey")
        cold = [runner.execute(sql % k).rows for k in (3, 7)]
        warm = [runner.execute(sql % k, properties=props).rows
                for k in (3, 7)]
        assert warm == cold
        h0 = _metric("result_cache_hit_total")
        again = [runner.execute(sql % k, properties=props).rows
                 for k in (3, 7)]
        assert again == cold
        assert _metric("result_cache_hit_total") == h0 + 2
    finally:
        for w in workers:
            w.stop()


def test_serving_cache_suite_lock_graph_clean():
    """End-of-suite assertion (ISSUE 15): the template/result cache
    locks are `checked_lock`s, so everything this module exercised —
    template builds, result-cache hits/partials, cluster parity —
    recorded real acquisition edges; the observed graph must hold no
    cycle, no jit dispatch under a lock, and no guarded-field
    violation. Defined last: pytest runs in definition order."""
    from presto_tpu._devtools import lockcheck
    assert lockcheck.ENABLED
    assert lockcheck.GRAPH.check() == [], lockcheck.GRAPH.check()
