"""Serving plane: plan cache, group-weighted device scheduling, group
memory accounting, shared-scan batching, queued timeouts, and the
32-query concurrency stress test (ISSUE 9 acceptance)."""
import threading
import time

import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.serving.plancache import PLANS, PlanCache


@pytest.fixture()
def runner():
    r = LocalRunner(tpch_sf=0.001)
    yield r


def _metric(name: str) -> float:
    from presto_tpu.obs.metrics import REGISTRY
    for m in REGISTRY.snapshot():
        if m["name"] == name:
            return float(m["value"])
    return 0.0


# -- plan cache ---------------------------------------------------------------

def test_plan_cache_repeated_statement_hits(runner):
    sql = "select count(*) from nation where n_regionkey = 1"
    h0, m0 = _metric("plan_cache_hit_total"), _metric("plan_cache_miss_total")
    first = runner.execute(sql).rows
    second = runner.execute(sql).rows
    assert first == second
    assert _metric("plan_cache_miss_total") == m0 + 1
    assert _metric("plan_cache_hit_total") == h0 + 1


def test_plan_cache_execute_skips_replan(runner):
    runner.execute("prepare dash from "
                   "select count(*) from orders where o_totalprice > ?")
    h0 = _metric("plan_cache_hit_total")
    a = runner.execute("execute dash using 1000").rows
    b = runner.execute("execute dash using 1000").rows
    assert a == b
    # the second EXECUTE of identical arguments rides the cached plan
    assert _metric("plan_cache_hit_total") == h0 + 1
    # different arguments are a different fingerprint: re-planned under
    # the new binding, never served the other binding's plan
    assert runner.execute("execute dash using 999999999").rows == [(0,)]


def test_plan_cache_invalidated_by_write(runner):
    runner.execute("create table memory.t1 as select 1 as x")
    sql = "select count(*) from memory.t1"
    assert runner.execute(sql).rows == [(1,)]
    i0 = _metric("plan_cache_invalidated_total")
    runner.execute("insert into memory.t1 select 2")
    # the write invalidated the cached plan (eager hook) and the re-run
    # sees the new row — never a stale plan over stale stats
    assert runner.execute(sql).rows == [(2,)]
    assert _metric("plan_cache_invalidated_total") >= i0 + 1


def test_plan_cache_property_sensitivity(runner):
    """A session-property overlay is part of the fingerprint: toggling
    an optimizer gate must not serve the other variant's plan."""
    sql = "select count(*) from lineitem where l_quantity > 20"
    base = runner.execute(sql).rows
    off = runner.execute(sql,
                         properties={"dense_grouping": False}).rows
    assert base == off


def test_plan_cache_disabled_by_session_prop(runner):
    sql = "select count(*) from region"
    h0 = _metric("plan_cache_hit_total")
    m0 = _metric("plan_cache_miss_total")
    runner.execute(sql, properties={"plan_cache": False})
    runner.execute(sql, properties={"plan_cache": False})
    assert _metric("plan_cache_hit_total") == h0
    assert _metric("plan_cache_miss_total") == m0


def test_plan_cache_uncacheable_system_tables(runner):
    """system.runtime tables have no data version: never cached."""
    sql = "select count(*) from system.runtime.metrics"
    runner.execute(sql)
    h0 = _metric("plan_cache_hit_total")
    runner.execute(sql)
    assert _metric("plan_cache_hit_total") == h0


def test_plan_cache_lru_eviction():
    pc = PlanCache(capacity=2)

    class _Plan:
        def __init__(self):
            self.root = type("N", (), {"children": ()})()
            self.init_plans = []

    class _Sess:
        class catalogs:
            @staticmethod
            def get(name):
                raise AssertionError("no scans, no deps")
    for i in range(3):
        # dep-free plans (no scans) cache unconditionally
        assert pc.put(bytes([i]), _Plan(), _Sess())
    assert len(pc) == 2
    assert pc.get(bytes([0])) is None      # oldest evicted
    assert pc.get(bytes([2])) is not None


# -- group-weighted fair device scheduling ------------------------------------

def test_group_weighted_quanta_ratio():
    """ISSUE 9 acceptance: under saturation a 2-weight group receives
    >= 1.5x the device quanta of a 1-weight group."""
    from presto_tpu.exec.taskexec import DeviceScheduler

    sched = DeviceScheduler()
    stop = threading.Event()
    counts = {"heavy": 0, "light": 0}
    lock = threading.Lock()

    def worker(group: str, weight: int) -> None:
        h = sched.task(name=f"{group}-t", group=group, weight=weight)
        try:
            while not stop.is_set():
                sched.run_quantum(h, lambda: time.sleep(0.002))
                with lock:
                    counts[group] += 1
        finally:
            h.close()

    threads = [threading.Thread(target=worker, args=("heavy", 2)),
               threading.Thread(target=worker, args=("heavy", 2)),
               threading.Thread(target=worker, args=("light", 1)),
               threading.Thread(target=worker, args=("light", 1))]
    for t in threads:
        t.start()
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert counts["light"] > 0, counts
    ratio = counts["heavy"] / counts["light"]
    assert ratio >= 1.5, counts
    shares = sched.group_shares()
    assert shares["heavy"]["device_seconds"] > \
        shares["light"]["device_seconds"]


def test_group_share_registry_bounded():
    """Idle shares beyond the cap evict: restart-per-tenant churn must
    not grow the scheduler's ledger (or the snapshot denominator)
    forever."""
    from presto_tpu.exec.taskexec import _MAX_SHARES, DeviceScheduler

    sched = DeviceScheduler()
    for i in range(_MAX_SHARES + 50):
        sched.task(name=f"t{i}", group=f"rg{i}/g").close()
    live = sched.task(name="live", group="keep/g")
    assert len(sched.group_shares()) <= _MAX_SHARES + 1
    assert "keep/g" in sched.group_shares()   # active share survives
    live.close()


def test_group_share_idle_return_clamp():
    """A group returning from idle competes from the active floor — it
    cannot replay its idle period as debt and monopolize the device."""
    from presto_tpu.exec.taskexec import DeviceScheduler

    sched = DeviceScheduler()
    a = sched.task(name="a", group="ga", weight=1)
    for _ in range(20):
        sched.run_quantum(a, lambda: time.sleep(0.001))
    # group gb was idle the whole time; its share starts at ga's vtime
    b = sched.task(name="b", group="gb", weight=1)
    shares = sched.group_shares()
    assert shares["gb"]["vtime"] >= shares["ga"]["vtime"] * 0.99
    a.close()
    b.close()


# -- group memory accounting --------------------------------------------------

def _group_manager(**leaf):
    from presto_tpu.server.resource_groups import ResourceGroupManager
    return ResourceGroupManager({
        "rootGroups": [{"name": "g", "hardConcurrencyLimit": 8,
                        "maxQueued": 100, **leaf}],
        "selectors": [{"group": "g"}]})


def test_group_memory_charges_and_refunds():
    from presto_tpu.serving.groups import QueryServingContext
    m = _group_manager(softMemoryLimit=1000)
    adm = m.submit()
    ctx = QueryServingContext(adm.group)
    ctx.charge(600)
    assert adm.group.memory_reserved == 600
    assert not adm.group.over_soft_memory()
    ctx.charge(600)
    assert adm.group.over_soft_memory()
    # over the soft limit the group queues new work
    adm2 = m.submit()
    assert not adm2.granted
    # refund wakes the dispatcher: the queued query is admitted
    ctx.close()
    assert adm.group.memory_reserved == 0
    assert adm2.granted
    adm2.release()
    adm.release()


def test_group_hard_memory_limit_kills_requester():
    from presto_tpu.memory import MemoryLimitExceeded, QueryMemoryPool
    from presto_tpu.serving.groups import QueryServingContext
    m = _group_manager(hardMemoryLimit=1 << 20)
    adm = m.submit()
    ctx = QueryServingContext(adm.group)
    pool = QueryMemoryPool(group=ctx)
    opctx = pool.context("op")
    pool.reserve(1 << 19, opctx)
    with pytest.raises(MemoryLimitExceeded) as ei:
        pool.reserve(1 << 20, opctx)
    assert "resource group" in str(ei.value)
    # the failed reservation left both ledgers consistent
    assert pool.reserved == 1 << 19
    assert adm.group.memory_reserved == 1 << 19
    opctx.close()
    assert adm.group.memory_reserved == 0
    ctx.close()
    adm.release()


def test_group_memory_via_protocol_query():
    """End to end: a protocol query's pool reservations land on the
    admitting group and return to zero afterwards."""
    from presto_tpu.server.protocol import PrestoTpuServer

    srv = PrestoTpuServer(LocalRunner(tpch_sf=0.001))
    try:
        q = srv.create_query(
            "select l_returnflag, sum(l_quantity) from lineitem "
            "group by l_returnflag", {})
        q.done.wait(timeout=30)
        assert q.state == "FINISHED"
        root = srv.resource_groups.roots["global"]
        assert root.memory_reserved == 0
        assert root.running == 0
    finally:
        srv.stop()


# -- admission: leak regression + queued timeout ------------------------------

def test_failed_query_releases_admission_slot():
    """ISSUE 9 satellite: a query that fails during planning/execution
    must release its resource-group slot on every exit path."""
    from presto_tpu.server.protocol import PrestoTpuServer

    srv = PrestoTpuServer(LocalRunner(tpch_sf=0.001))
    try:
        q = srv.create_query("select bogus_column from nation", {})
        q.done.wait(timeout=30)
        assert q.state == "FAILED"
        info = srv.resource_groups.info()[0]
        assert info["numRunning"] == 0 and info["numQueued"] == 0
        # and the next query is admitted normally
        q2 = srv.create_query("select 1", {})
        q2.done.wait(timeout=30)
        assert q2.state == "FINISHED"
    finally:
        srv.stop()


def test_query_queued_timeout():
    """A query stuck in the admission queue past its deadline fails
    with a distinct QUERY_QUEUED_TIMEOUT verdict (and frees its queue
    slot), instead of waiting forever."""
    from presto_tpu.server.protocol import PrestoTpuServer

    class SlowRunner:
        def __init__(self):
            self.gate = threading.Event()
            self.started = threading.Event()
            from presto_tpu.exec.local import QueryResult
            self._result = QueryResult(["x"], [], [(1,)])

        def execute(self, sql, properties=None, user="",
                    cancel_event=None):
            if sql == "slow":
                self.started.set()
                self.gate.wait(20)
            return self._result

    runner = SlowRunner()
    srv = PrestoTpuServer(runner=runner)   # serial default group
    try:
        q1 = srv.create_query("slow", {})
        # producers run on a shared pool: without this rendezvous q2
        # can win the serial slot before q1 is admitted (and FINISH
        # instead of timing out) — wait until q1 actually holds it
        assert runner.started.wait(10)
        q2 = srv.create_query("fast", {"query_queued_timeout": "0.3s"})
        q2.done.wait(timeout=10)
        assert q2.state == "FAILED"
        assert q2.error["errorName"] == "QUERY_QUEUED_TIMEOUT"
        info = srv.resource_groups.info()[0]
        assert info["numQueued"] == 0
        runner.gate.set()
        q1.done.wait(timeout=10)
        assert q1.state == "FINISHED"
        assert info["numRunning"] in (0, 1)  # q1 may still be draining
    finally:
        runner.gate.set()
        srv.stop()


def test_group_config_queued_timeout():
    from presto_tpu.server.resource_groups import ResourceGroupManager
    m = ResourceGroupManager({
        "rootGroups": [{"name": "g", "hardConcurrencyLimit": 1,
                        "queryQueuedTimeout": "250ms"}],
        "selectors": [{"group": "g"}]})
    a = m.submit()
    b = m.submit()
    assert b.queued_timeout_s() == pytest.approx(0.25)
    # session override wins over the group config
    assert b.queued_timeout_s("2s") == pytest.approx(2.0)
    b.release()
    a.release()


# -- shared-scan batching -----------------------------------------------------

def test_shared_scan_single_decode():
    """N concurrent misses on one split ride ONE decode: the connector
    sees one page_source call, every query gets full results."""
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector

    class CountingConnector:
        def __init__(self, inner):
            self._inner = inner
            self.name = inner.name
            self.decodes_by_split = {}
            self._lock = threading.Lock()
            self._gate = threading.Event()

        @property
        def metadata(self):
            return self._inner.metadata

        @property
        def split_manager(self):
            return self._inner.split_manager

        def data_version(self, table):
            return self._inner.data_version(table)

        def page_source(self, split, columns, pushdown=None,
                        rows_per_batch=1 << 17):
            with self._lock:
                key = (split.table.table, split.info)
                self.decodes_by_split[key] = \
                    self.decodes_by_split.get(key, 0) + 1
            inner = self._inner.page_source(
                split, columns, pushdown=pushdown,
                rows_per_batch=rows_per_batch)
            gate = self._gate

            class _PS:
                def batches(self):
                    for b in inner.batches():
                        # slow decode: attached queries must wait on
                        # this in-flight decode, not start their own
                        gate.wait(0.05)
                        yield b
            return _PS()

    conn = CountingConnector(TpchConnector(sf=0.001))
    catalogs = CatalogManager()
    catalogs.register("tpch", conn)
    runner = LocalRunner(catalogs=catalogs)
    sql = "select count(*), sum(o_totalprice) from orders"
    a0 = _metric("scan_shared_attach_total")

    results, errors = [], []

    def go():
        try:
            results.append(runner.execute(
                sql, properties={"plan_cache": False}).rows)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=go) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert len(results) == 4
    assert all(r == results[0] for r in results)
    # exactly ONE decode per split across all 4 queries; the rest
    # attached to the in-flight decode or replayed the inserted entry
    # (both are shared-work wins; what must not happen is 4x decodes)
    assert conn.decodes_by_split, "no scans observed"
    assert all(n == 1 for n in conn.decodes_by_split.values()), \
        conn.decodes_by_split
    assert _metric("scan_shared_attach_total") >= a0


def test_shared_scan_owner_failure_recovers():
    """If the owning decode dies, attached queries retry and succeed."""
    from presto_tpu.exec.scancache import CACHE

    key = ("synthetic-inflight-key",)
    fl, owner = CACHE.join_inflight(key)
    assert owner
    got = []

    def waiter():
        rec, own = CACHE.join_inflight(key)
        assert not own
        rec.event.wait(5)
        got.append(rec.batches)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    CACHE.finish_inflight(key, None)    # owner failed
    t.join(timeout=5)
    assert got == [None]                # waiter told to retry
    # registry is clean: the next joiner becomes owner again
    fl2, owner2 = CACHE.join_inflight(key)
    assert owner2
    CACHE.finish_inflight(key, None)


# -- cluster path through admission + plan cache (ISSUE 10 satellite) ---------

def test_cluster_runner_through_admission_and_plan_cache():
    """The statement server fronts a ClusterRunner with the SAME
    resource-group admission, serving handoff, and compiled-plan cache
    that LocalRunner deployments get: repeated statements skip
    parse/plan/optimize, the admitting group's slot frees on every
    exit path, and the query's device quanta bill the group's
    scheduler share on the (in-process) workers."""
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.server.protocol import (
        PrestoTpuServer, _runner_accepts_serving,
    )
    from presto_tpu.server.worker import WorkerServer

    workers = [WorkerServer(tpch_sf=0.001) for _ in range(2)]
    for w in workers:
        w.start()
    urls = [f"http://127.0.0.1:{w.port}" for w in workers]
    runner = ClusterRunner(urls, tpch_sf=0.001, heartbeat=False)
    assert _runner_accepts_serving(runner)
    srv = PrestoTpuServer(runner=runner, resource_groups={
        "rootGroups": [{"name": "fleet", "hardConcurrencyLimit": 2,
                        "schedulingWeight": 3}],
        "selectors": [{"group": "fleet"}]})
    try:
        sql = ("select n_regionkey, count(*) c from nation "
               "group by n_regionkey order by n_regionkey")
        h0 = _metric("plan_cache_hit_total")
        q1 = srv.create_query(sql, {}, user="alice")
        q1.done.wait(timeout=60)
        assert q1.state == "FINISHED", q1.error
        q2 = srv.create_query(sql, {}, user="alice")
        q2.done.wait(timeout=60)
        assert q2.state == "FINISHED", q2.error
        # the repeated statement rode the compiled-plan cache on the
        # CLUSTER path
        assert _metric("plan_cache_hit_total") >= h0 + 1
        # admission accounting drained on every exit path
        info = srv.resource_groups.info()[0]
        assert info["numRunning"] == 0 and info["numQueued"] == 0
        # the admitting group's stride share exists on the device
        # scheduler (the worker-side serving handoff landed)
        from presto_tpu.exec.taskexec import GLOBAL
        assert any(k.endswith("/fleet")
                   for k in GLOBAL.group_shares()), \
            GLOBAL.group_shares().keys()
        # per-query session property overlays reach the cluster
        # session (a bad value fails the statement, a good one binds)
        q3 = srv.create_query(sql, {"retry_policy": "BOGUS"})
        q3.done.wait(timeout=60)
        assert q3.state == "FAILED"
        q4 = srv.create_query(sql, {"retry_policy": "NONE"})
        q4.done.wait(timeout=60)
        assert q4.state == "FINISHED", q4.error
    finally:
        srv.stop()
        for w in workers:
            try:
                w.stop()
            except Exception:
                pass


# -- concurrency stress test --------------------------------------------------

def test_concurrent_stress_parity_and_fairness():
    """ISSUE 9 satellite: ~32 mixed queries (repeated + distinct, two
    groups) concurrently against one server == serial results, with
    plan-cache hits observed and a clean lock-order graph."""
    from presto_tpu.client import StatementClient
    from presto_tpu.server.protocol import PrestoTpuServer

    runner = LocalRunner(tpch_sf=0.001)
    srv = PrestoTpuServer(runner, resource_groups={
        "rootGroups": [
            {"name": "root", "hardConcurrencyLimit": 8,
             "maxQueued": 1000,
             "subGroups": [
                 {"name": "etl", "hardConcurrencyLimit": 8,
                  "schedulingWeight": 2},
                 {"name": "adhoc", "hardConcurrencyLimit": 8,
                  "schedulingWeight": 1}]}],
        "selectors": [{"user": "etl-.*", "group": "root.etl"},
                      {"group": "root.adhoc"}]})
    srv.start()
    statements = [
        "select count(*) from lineitem where l_quantity > 25",
        "select l_returnflag, count(*) from lineitem "
        "group by l_returnflag order by l_returnflag",
        "select count(*) from orders where o_totalprice > 1000",
        "select n_name from nation order by n_name limit 3",
        "select r_name, count(*) from region group by r_name "
        "order by r_name",
        "select max(o_orderdate) from orders",
        "select count(distinct l_suppkey) from lineitem",
        "select sum(l_extendedprice * (1 - l_discount)) from lineitem "
        "where l_shipdate > date '1995-01-01'",
    ]
    try:
        # serial oracle (one execution per distinct statement)
        serial = {}
        oracle = StatementClient(f"http://127.0.0.1:{srv.port}",
                                 user="oracle")
        for s in statements:
            serial[s] = oracle.execute(s).rows
        h0 = _metric("plan_cache_hit_total")

        results, errors = {}, []
        lock = threading.Lock()

        def client(ci: int) -> None:
            user = f"etl-{ci}" if ci % 2 == 0 else f"adhoc-{ci}"
            cl = StatementClient(f"http://127.0.0.1:{srv.port}",
                                 user=user)
            sql = statements[ci % len(statements)]
            try:
                rows = cl.execute(sql).rows
                with lock:
                    results.setdefault(sql, []).append(rows)
            except Exception as e:
                errors.append(f"{ci}: {e}")

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        # row-exact parity with serial execution, for every client
        total = 0
        for sql, outs in results.items():
            for rows in outs:
                assert rows == serial[sql], sql
                total += 1
        assert total == 32
        # repeated statements rode the plan cache
        assert _metric("plan_cache_hit_total") > h0
        # both groups ran work and drained clean
        info = srv.resource_groups.info()[0]
        assert info["numRunning"] == 0 and info["numQueued"] == 0
        rows = runner.execute(
            "select \"group\", running, queued from "
            "system.runtime.resource_groups").rows
        groups = {r[0] for r in rows}
        assert {"root", "root.etl", "root.adhoc"} <= groups
        # no lock-discipline violations under full concurrency
        from presto_tpu._devtools import lockcheck
        assert lockcheck.ENABLED
        assert lockcheck.GRAPH.check() == [], lockcheck.GRAPH.check()
    finally:
        srv.stop()


def test_serving_suite_lock_graph_clean():
    """End-of-suite assertion (ISSUE 15): the serving plane's locks —
    plan cache, producer pool, query page/state, resource-group
    manager/memory, group registry — are `checked_lock`s, so every
    edge this module's admission/scheduling/batching stress recorded is
    in the process graph; it must hold no cycle, no jit dispatch under
    a lock, and no guarded-field violation. Defined last: pytest runs
    in definition order."""
    from presto_tpu._devtools import lockcheck
    assert lockcheck.ENABLED
    assert lockcheck.GRAPH.check() == [], lockcheck.GRAPH.check()
