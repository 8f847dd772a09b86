"""ISSUE 19: coordinator-fleet cache coherence.

Two statement servers ("coordinators") in one process, each over its
OWN LocalRunner and OWN CatalogManager, sharing one writable sqlite
catalog file — the in-process stand-in for a multi-process fleet (the
subprocess version runs in the chaos drill).
Connector identity keeps the stand-in honest: each coordinator's
caches stamp deps against its own connector OBJECT, so a write through
A can only reach B's template/result entries via the fleet bump
broadcast -> ``fold_bump`` -> ``spi.notify_data_change`` path, exactly
like separate processes.

Covers the three coherence contracts:

- a write through coordinator A invalidates B's template + result
  entries BEFORE B's next hit (eager remote invalidation, observed via
  the invalidation counters and row-exact reads);
- with broadcasts dropped (the ``fleet.broadcast`` failpoint), B still
  serves row-exact results — the hit-time ``data_version``
  revalidation backstop (sqlite's PRAGMA data_version sees foreign
  commits);
- the remote-bump-vs-local-insert race, interleaving-explored: a bump
  folding between B's epoch capture and its cache insert must veto the
  insert (the epoch-before-deps contract holds across the wire).
"""
import os
import sqlite3
import tempfile

import pytest

from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.spi import CatalogManager
from presto_tpu.connectors.sqlite import SqliteConnector
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.serving.fleet import FleetMember

CACHE_PROPS = {"plan_template_cache": True, "result_cache": True}


def _metric(name: str) -> float:
    for m in REGISTRY.snapshot():
        if m["name"] == name:
            return m["value"]
    return 0.0


def _make_runner(db_path: str) -> LocalRunner:
    cats = CatalogManager()
    cats.register("memory", MemoryConnector())
    cats.register("fleetdb", SqliteConnector(db_path))
    r = LocalRunner(catalogs=cats, catalog="fleetdb")
    r.session.properties.update(CACHE_PROPS)
    return r


@pytest.fixture()
def fleet_pair(tmp_path):
    """Two HTTP coordinators, fleet-enabled, over one sqlite file."""
    from presto_tpu.server.protocol import PrestoTpuServer
    db = str(tmp_path / "shared.db")
    servers = []
    for i in range(2):
        srv = PrestoTpuServer(_make_runner(db))
        srv.start()
        servers.append(srv)
    urls = [f"http://127.0.0.1:{s.port}" for s in servers]
    for i, srv in enumerate(servers):
        srv.enable_fleet(f"coord-{i}",
                         peers=[u for j, u in enumerate(urls) if j != i],
                         heartbeat_s=5.0)
    try:
        yield servers, urls, db
    finally:
        for srv in servers:
            try:
                srv.kill()
            except Exception:
                pass


def _client(url):
    from presto_tpu.client import StatementClient
    return StatementClient(url, user="fleet-test")


def test_remote_write_invalidates_before_next_hit(fleet_pair):
    """Write through A -> B's template + result entries drop eagerly
    (the broadcast fold), before B's next lookup — and B's re-read is
    row-exact against an uncached run."""
    servers, urls, _db = fleet_pair
    a, b = _client(urls[0]), _client(urls[1])
    a.execute("create table fleetdb.default.t1 as select 1 as x")
    sql = ("select count(*) as c, sum(x) as s "
           "from fleetdb.default.t1 where x < 100")
    h0 = _metric("result_cache_hit_total")
    r1 = b.execute(sql).rows
    r2 = b.execute(sql).rows
    assert r1 == r2 == [[1, 1]]
    assert _metric("result_cache_hit_total") == h0 + 1
    # a second binding of the same template (different literal) is a
    # template hit — B now holds template AND result entries
    th0 = _metric("plan_template_cache_hit_total")
    b.execute("select count(*) as c, sum(x) as s "
              "from fleetdb.default.t1 where x < 200")
    assert _metric("plan_template_cache_hit_total") > th0

    ri0 = _metric("result_cache_invalidated_total")
    ti0 = _metric("plan_template_cache_invalidated_total")
    f0 = _metric("fleet_bump_fold_total")
    a.execute("insert into fleetdb.default.t1 select 2 as x")
    # the bump POST rides A's write synchronously; B folded it through
    # spi.notify_data_change before A's statement even finished
    assert _metric("fleet_bump_fold_total") > f0
    assert _metric("result_cache_invalidated_total") > ri0
    assert _metric("plan_template_cache_invalidated_total") > ti0
    # B serves the post-write truth — and it is a rebuild, not a hit
    h1 = _metric("result_cache_hit_total")
    assert b.execute(sql).rows == [[2, 3]]
    assert _metric("result_cache_hit_total") == h1


def test_dropped_broadcast_still_serves_correct_rows(fleet_pair):
    """The fail-safe backstop: with every broadcast dropped at the
    ``fleet.broadcast`` failpoint, B never hears about A's write — but
    its hit-time data_version revalidation (sqlite PRAGMA data_version
    moves on foreign commits) refuses the stale entry and recomputes
    row-exact results."""
    from presto_tpu.exec.failpoints import FAILPOINTS
    servers, urls, _db = fleet_pair
    a, b = _client(urls[0]), _client(urls[1])
    a.execute("create table fleetdb.default.t2 as select 10 as x")
    sql = "select count(*) as c, sum(x) as s from fleetdb.default.t2"
    assert b.execute(sql).rows == [[1, 10]]
    h0 = _metric("result_cache_hit_total")
    assert b.execute(sql).rows == [[1, 10]]
    assert _metric("result_cache_hit_total") == h0 + 1

    FAILPOINTS.configure("fleet.broadcast", action="error",
                         message="chaos: broadcast dropped")
    try:
        d0 = _metric("fleet_bump_dropped_total")
        f0 = _metric("fleet_bump_fold_total")
        a.execute("insert into fleetdb.default.t2 select 20 as x")
        assert _metric("fleet_bump_dropped_total") > d0
        assert _metric("fleet_bump_fold_total") == f0   # B never told
        # B's cached entry survived (no eager invalidation) — the
        # lookup itself must notice the drifted data_version
        assert b.execute(sql).rows == [[2, 30]]
    finally:
        FAILPOINTS.clear("fleet.broadcast")
    # and once broadcasts flow again, coherence is eager once more
    f1 = _metric("fleet_bump_fold_total")
    a.execute("insert into fleetdb.default.t2 select 30 as x")
    assert _metric("fleet_bump_fold_total") > f1
    assert b.execute(sql).rows == [[3, 60]]


def test_fold_is_deduped_and_catalog_checked(tmp_path):
    """fold_bump unit seams: per-origin monotonic dedupe, unknown
    catalogs counted and ignored, own-origin bumps refused."""
    db = str(tmp_path / "f.db")
    cats = CatalogManager()
    cats.register("fleetdb", SqliteConnector(db))
    m = FleetMember("coord-b", "http://127.0.0.1:0", catalogs=cats)
    doc = {"origin": "coord-a", "seq": 1, "connectorId": "fleetdb",
           "table": "t"}
    assert m.fold_bump(dict(doc)) is True
    s0 = _metric("fleet_bump_stale_total")
    assert m.fold_bump(dict(doc)) is False          # replayed seq
    assert _metric("fleet_bump_stale_total") == s0 + 1
    assert m.fold_bump(dict(doc, seq=2)) is True    # monotonic advance
    u0 = _metric("fleet_bump_unknown_catalog_total")
    assert m.fold_bump(dict(doc, seq=3,
                            connectorId="nosuch")) is False
    assert _metric("fleet_bump_unknown_catalog_total") == u0 + 1
    assert m.fold_bump(dict(doc, origin="coord-b", seq=9)) is False


def test_remote_bump_vs_local_insert_interleaving(tmp_path):
    """The cross-the-wire epoch-before-deps race, systematically
    explored: coordinator B runs a cacheable SELECT while a remote
    write (raw sqlite commit, then ``fold_bump``) lands at every
    schedulable seam. No interleaving may leave a stale entry — the
    fold's notify bumps the write epoch, and an insert whose epoch
    predates it is vetoed."""
    from presto_tpu._devtools.interleave import (explore,
                                                 failpoints_as_points,
                                                 point)

    def make():
        db = os.path.join(tempfile.mkdtemp(dir=tmp_path), "race.db")
        r = _make_runner(db)
        member = FleetMember("coord-b", "http://127.0.0.1:0",
                             catalogs=r.session.catalogs)
        r.execute("create table fleetdb.default.rt as select 1 as x")
        sql = ("select count(*) as c, sum(x) as s "
               "from fleetdb.default.rt")

        def reader():
            r.execute(sql, properties=CACHE_PROPS)

        def remote_writer():
            point("remote.write")
            raw = sqlite3.connect(db)
            raw.execute("insert into rt values (2)")
            raw.commit()
            raw.close()
            point("remote.bump")
            member.fold_bump({"origin": "coord-a", "seq": 1,
                              "connectorId": "fleetdb",
                              "table": "rt"})

        def check():
            got = r.execute(sql, properties=CACHE_PROPS).rows
            want = r.execute(sql).rows
            if got != want:
                return f"stale cached rows {got} vs truth {want}"
            return None

        return [reader, remote_writer], check

    with failpoints_as_points(["plancache.plan", "resultcache.stamp"]):
        ex = explore(make, max_schedules=48, preemption_bound=2)
    assert ex.schedules, "explorer executed no schedules"
    ex.assert_clean()


def test_explicit_deregister_beats_the_staleness_grace():
    """ISSUE 20 scale-down race: a DRAINED coordinator must leave the
    survivor's peer list and federated counts the moment its final
    ``leaving`` heartbeat folds — not ``staleness_grace_s`` later, and
    never declared lost. A KILLED coordinator (no leaving heartbeat)
    keeps holding its admission share until the grace expires, then is
    declared lost exactly once. With the grace set huge, only the
    explicit path can possibly clear state — the regression this pins:
    an autoscaler that drains and instantly relaunches must never see
    the old member's ghost counts bind admission against the new one."""
    survivor = FleetMember("coord-0", "http://127.0.0.1:9100",
                           staleness_grace_s=3600.0)
    lost0 = _metric("coordinator_lost_total")

    def hb(origin, url, leaving=False):
        return {"origin": origin, "url": url, "leaving": leaving,
                "groups": {"serving": {"running": 7, "memory": 0}}}

    # two members join: their counts bind admission, urls enter peering
    survivor.fold_heartbeat(hb("coord-drain", "http://127.0.0.1:9101"))
    survivor.fold_heartbeat(hb("coord-kill", "http://127.0.0.1:9102"))
    assert survivor.remote_running("serving") == 14
    assert set(survivor.peers()) == {"http://127.0.0.1:9101",
                                     "http://127.0.0.1:9102"}

    # clean drain: one leaving heartbeat clears EVERYTHING now
    survivor.fold_heartbeat(hb("coord-drain", "http://127.0.0.1:9101",
                               leaving=True))
    st = survivor.status()
    assert survivor.remote_running("serving") == 7
    assert st["peers"] == ["http://127.0.0.1:9102"]
    assert "coord-drain" not in st["remote"]
    assert st["lost"] == []                      # a drain is NOT a loss
    assert _metric("coordinator_lost_total") == lost0

    # killed member: counts persist inside the grace...
    survivor._sweep_lost()
    assert survivor.remote_running("serving") == 7
    assert "coord-kill" in survivor.status()["remote"]
    # ...and only expiring the grace declares the loss (once)
    survivor.staleness_grace_s = 0.0
    survivor._sweep_lost()
    survivor._sweep_lost()
    st = survivor.status()
    assert st["lost"] == ["coord-kill"]
    assert survivor.remote_running("serving") == 0
    assert _metric("coordinator_lost_total") == lost0 + 1


def test_coordinators_over_one_worker_pool_number_queries_apart():
    """A fleet's coordinators share their workers, where tasks, spool
    directories and the end-of-query DELETE are keyed by query id: two
    ClusterRunners must never give their n-th queries one id, or one
    coordinator's clean-up takes the other's rows
    (``test_fleet_coordinator_kill``, about one run in ten)."""
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.server.worker import WorkerServer
    worker = WorkerServer(tpch_sf=0.001)
    worker.start()
    try:
        url = f"http://127.0.0.1:{worker.port}"
        sql = "select count(*) from nation"
        ids = []
        for _ in range(2):
            r = ClusterRunner([url], tpch_sf=0.001, heartbeat=False)
            assert [tuple(x) for x in r.execute(sql).rows] == [(25,)]
            ids.append([e.query_id for e in r.local.query_log
                        if e.query_id.startswith("cq_")][-1])
        assert ids[0] != ids[1]
    finally:
        worker.stop()
