"""Mesh-native execution by default (PR 12): auto-selection + parity.

The tentpole contract: with more than one device visible, the RUNNER
entry points (LocalRunner.execute / ClusterRunner.execute — never a
direct DistributedExecutor call) place SQL on the SPMD mesh by default
(`mesh_execution=auto`), with row-exact parity against the
single-device path and `mesh_execution=off` as the escape hatch. The
harness pins the environment default off (tests/conftest.py) so only
these suites pay shard_map compiles; every test here opts back in per
query through the session-property overlay, which is exactly the
production surface.
"""
import jax
import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY

SF = 0.005

AUTO = {"mesh_execution": "auto"}
OFF = {"mesh_execution": "off"}

#: the parity sweep shapes: joins, grouped aggs, top-n, semi joins,
#: NULL-heavy inputs (outer-join NULL extension + NULL-aware anti join)
SWEEP = [
    ("grouped-agg", "select o_orderstatus, count(*), sum(o_totalprice) "
                    "from orders group by 1 order by 1"),
    ("join-agg-topn", "select c_name, sum(o_totalprice) from customer "
                      "join orders on c_custkey = o_custkey "
                      "group by 1 order by 2 desc, 1 limit 3"),
    ("semi", "select count(*) from orders where o_custkey in "
             "(select c_custkey from customer where c_acctbal > 0)"),
    ("null-left-join", "select s_name, n_name from supplier left join "
                       "nation on s_nationkey = n_nationkey "
                       "and n_regionkey < 2 order by 1, 2 limit 8"),
    ("null-anti", "select count(*) from orders where o_custkey not in "
                  "(select case when c_acctbal < 0 then null "
                  "else c_custkey end from customer)"),
    ("distinct", "select distinct c_mktsegment from customer "
                 "order by 1"),
]


def _metric(name: str) -> float:
    return REGISTRY.value(name)


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=SF, rows_per_batch=1 << 11)


def _norm(rows):
    out = []
    for r in rows:
        out.append(tuple(v.item() if hasattr(v, "item") else v
                         for v in r))
    return out


def _check_parity(want, got, ordered):
    w, g = _norm(want.rows), _norm(got.rows)
    if not ordered:
        w, g = sorted(w, key=repr), sorted(g, key=repr)
    assert len(g) == len(w)
    for gr, wr in zip(g, w):
        for gv, wv in zip(gr, wr):
            if isinstance(gv, float):
                assert gv == pytest.approx(wv, rel=1e-6, abs=1e-9)
            else:
                assert gv == wv, (gr, wr)


def _parity(runner, sql, props_on, extra=None):
    props_off = {**OFF, **(extra or {})}
    props_on = {**props_on, **(extra or {})}
    want = runner.execute(sql, properties=props_off)
    got = runner.execute(sql, properties=props_on)
    _check_parity(want, got, "order by" in sql.lower())
    return got


def test_auto_selects_mesh_and_matches(runner):
    """The default: >1 device -> SQL lands on the mesh (observable as
    mesh_path_selected_total) with rows matching the local path."""
    before = _metric("mesh_path_selected_total")
    _parity(runner, SWEEP[0][1], {**AUTO, "mesh_devices": 2})
    assert _metric("mesh_path_selected_total") == before + 1


def test_off_escape_hatch_stays_local(runner):
    before = _metric("mesh_path_selected_total")
    res = runner.execute(SWEEP[0][1], properties=dict(OFF))
    assert res.rows
    assert _metric("mesh_path_selected_total") == before


def test_mesh_devices_one_stays_local(runner):
    """mesh_devices=1 under auto means a 1-chip 'mesh' — the router
    keeps the plain single-device executor."""
    before = _metric("mesh_path_selected_total")
    res = runner.execute(SWEEP[0][1],
                         properties={**AUTO, "mesh_devices": 1})
    assert res.rows
    assert _metric("mesh_path_selected_total") == before


@pytest.mark.parametrize("name,sql", SWEEP[1:3],
                         ids=[t[0] for t in SWEEP[1:3]])
def test_parity_n2(runner, name, sql):
    _parity(runner, sql, {**AUTO, "mesh_devices": 2})


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 2, 8])
@pytest.mark.parametrize("name,sql", SWEEP, ids=[t[0] for t in SWEEP])
def test_parity_sweep(runner, n, name, sql):
    """The full sweep: every shape at n_devices in {1, 2, 8} through
    the runner entry point. n=1 exercises the router's 1-chip
    degenerate (local path); n>1 the real SPMD substrate."""
    _parity(runner, sql, {**AUTO, "mesh_devices": n})


def test_system_catalog_stays_local(runner):
    """Metadata queries gain nothing from SPMD: auto never meshes
    them."""
    before = _metric("mesh_path_selected_total")
    res = runner.execute(
        "select name from system.runtime.metrics limit 1",
        properties=dict(AUTO))
    assert res.rows is not None
    assert _metric("mesh_path_selected_total") == before


def test_mesh_stays_device_resident(runner, monkeypatch):
    """Transfer guard: a warm mesh query's intermediates never
    round-trip the host. Two teeth: the host staging fallback
    (_stage_parts) must not run — warm scans replay device-resident
    out of the scan cache and compose shards device-to-device — and
    the bytes fetched via jax.device_get stay at control-scalar scale
    (exchange quotas, error flags, result rows), independent of table
    size."""
    from presto_tpu.exec.distributed import DistributedExecutor
    sql = SWEEP[0][1]
    props = {**AUTO, "mesh_devices": 2}
    runner.execute(sql, properties=props)       # cold: compile + cache

    def no_host_staging(self, *a, **k):
        raise AssertionError("mesh scan staged through the host")

    monkeypatch.setattr(DistributedExecutor, "_stage_parts",
                        no_host_staging)
    fetched = []
    real = jax.device_get

    def counting(x):
        out = real(x)
        import numpy as np
        for leaf in jax.tree_util.tree_leaves(out):
            try:
                fetched.append(int(np.asarray(leaf).nbytes))
            except Exception:
                pass
        return out

    monkeypatch.setattr(jax, "device_get", counting)
    got = runner.execute(sql, properties=props)
    assert got.rows
    assert sum(fetched) < 64 * 1024, sum(fetched)


def test_scan_cache_serves_mesh(runner):
    """PR 4's device scan cache backs the mesh scan: a repeated mesh
    query replays decoded splits instead of re-decoding."""
    sql = "select count(*), sum(c_acctbal) from customer"
    props = {**AUTO, "mesh_devices": 2}
    runner.execute(sql, properties=props)
    before = _metric("scan_cache_hit_total")
    runner.execute(sql, properties=props)
    assert _metric("scan_cache_hit_total") > before


def test_adaptive_resplit_keeps_parity(runner, monkeypatch):
    """StageMonitor's skew verdict in action: with the threshold forced
    low, a partitioned join re-splits hot buckets mid-query (metric
    fires) and rows stay exact — the build side re-ships under the new
    assignment before the next probe batch."""
    from presto_tpu.exec import distributed as D
    monkeypatch.setattr(D, "_skew_ratio", lambda: 1.01)
    sql = ("select c_name, sum(o_totalprice) from customer join orders "
           "on c_custkey = o_custkey group by 1 order by 2 desc, 1 "
           "limit 5")
    before = _metric("mesh_repartition_resplit_total")
    _parity(runner, sql, {**AUTO, "mesh_devices": 2},
            extra={"broadcast_join_row_limit": 1})
    assert _metric("mesh_repartition_resplit_total") > before


def test_partition_map_rebalance_unit():
    """The greedy re-balancer itself: a hot bucket moves to the idle
    shard; a single hot KEY (one bucket) cannot improve and never
    flips; changes cap at MAX_CHANGES."""
    import numpy as np

    from presto_tpu.exec.distributed import _PartitionMap
    pm = _PartitionMap(2, ratio=1.5)
    counts = np.zeros((2, pm.buckets), dtype=np.int64)
    # buckets 0 and 2 both map to shard 0 initially (b % n): pile rows
    # on them so shard 0 holds ~all rows, then expect a re-split
    counts[0, 0] = 1000
    counts[0, 2] = 900
    counts[0, 1] = 10
    pm.observe(counts)
    assert pm.epoch == 1
    loads = [0, 0]
    for b, d in enumerate(pm.assign):
        loads[d] += int(pm._totals[b])
    assert max(loads) < 1900        # the two hot buckets split shards

    one_key = _PartitionMap(2, ratio=1.5)
    hot = np.zeros((2, one_key.buckets), dtype=np.int64)
    hot[0, 0] = 10_000              # one hot bucket: nothing to split
    one_key.observe(hot)
    assert one_key.epoch == 0

    capped = _PartitionMap(2, ratio=1.01)
    capped.changes = capped.MAX_CHANGES
    capped.observe(counts)
    assert capped.epoch == 0


def test_cluster_workerless_rides_mesh(runner):
    """A worker-less multi-chip ClusterRunner executes on the mesh
    (auto) instead of failing with no schedulable nodes."""
    from presto_tpu.exec.cluster import ClusterRunner
    cr = ClusterRunner(worker_urls=[], catalogs=runner.session.catalogs,
                       heartbeat=False)
    before = _metric("mesh_path_selected_total")
    got = cr.execute("select count(*) from nation",
                     properties={**AUTO, "mesh_devices": 2})
    assert _norm(got.rows) == [(25,)]
    assert _metric("mesh_path_selected_total") == before + 1


def test_mesh_runner_surface(runner, mesh_runner):
    """The statement surface holds on the mesh through the one front
    door (``LocalRunner.execute`` under ``mesh_execution = 'on'``): the
    shared fixture's first query takes the mesh path, per-query
    properties overlay, user lands in the history record, a pre-set
    cancel event interrupts. (An unknown property is refused where
    LocalRunner's callers validate: test_analyze's SET SESSION and
    test_protocol's header tests.)"""
    import threading

    from presto_tpu.errors import QueryCancelledError
    from presto_tpu.obs.history import HISTORY
    mr = mesh_runner(catalogs=runner.session.catalogs,
                     n_devices=2, rows_per_batch=1 << 11)
    before = _metric("mesh_path_selected_total")
    res = mr.execute("select count(*) from nation",
                     properties={"dense_grouping": True}, user="audit")
    assert _norm(res.rows) == [(25,)]
    assert _metric("mesh_path_selected_total") == before + 1
    rec = [h for h in HISTORY.snapshot() if h.get("user") == "audit"][-1]
    assert rec["query"] == "select count(*) from nation"
    assert rec["state"] == "FINISHED"
    ev = threading.Event()
    ev.set()
    with pytest.raises(QueryCancelledError):
        mr.execute("select count(*) from region", cancel_event=ev)


def test_mesh_execution_property_validates():
    from presto_tpu.config import (SessionPropertyError,
                                   validate_session_property)
    assert validate_session_property("mesh_execution", "AUTO") == "auto"
    assert validate_session_property("mesh_devices", "4") == 4
    with pytest.raises(SessionPropertyError):
        validate_session_property("mesh_execution", "sideways")


def test_mesh_stages_recipe():
    """The fragmenter's mesh-stage pass: a join+agg plan cuts into
    scan-shard / hash / single stages with the exchanges named."""
    from presto_tpu.planner.fragmenter import plan_mesh_stages
    r = LocalRunner(tpch_sf=0.001)
    plan = r.plan("select c_name, count(*) from customer join orders "
                  "on c_custkey = o_custkey group by 1")
    mp = plan_mesh_stages(plan.root)
    assert mp.supported
    kinds = [s.kind for s in mp.stages]
    assert kinds[-1] == "single"
    assert "scan-shard" in kinds
    exchanges = {s.exchange for s in mp.stages}
    assert "partition" in exchanges or "broadcast" in exchanges
    # partition exchanges feeding an agg/join consumer are marked as
    # fused into the consumer's shard_map program; everything else is
    # not (the root stage in particular has no exchange to fuse)
    for s in mp.stages:
        if s.fused:
            assert s.exchange == "partition"
    if "partition" in exchanges:
        assert any(s.fused for s in mp.stages)


def test_per_chip_billing(runner):
    """A mesh quantum bills every chip it occupies: the chip-quanta
    counter advances by the mesh width per quantum, and group device
    seconds grow accordingly (PR 8 tenants share the mesh fairly)."""
    before = _metric("scheduler_chip_quanta_total")
    bq = _metric("scheduler_quanta_total")
    runner.execute(SWEEP[0][1], properties={**AUTO, "mesh_devices": 2})
    dq = _metric("scheduler_quanta_total") - bq
    dchip = _metric("scheduler_chip_quanta_total") - before
    assert dq > 0 and dchip == 2 * dq
