"""Mesh flight recorder (obs/flight.py): per-round wall-clock
attribution for the SPMD exchange path.

The contract under test: every host-observable event on the mesh path
(dispatch, staging, control sync, re-split, repartition, prefetch
stall) lands in the active FlightRecorder as a timestamped round
record; `finish()` reconciles the round timeline against measured wall
into the six named buckets plus a per-shard critical path; and every
surface that re-renders the timeline — EXPLAIN ANALYZE's "Mesh rounds"
section, `system.runtime.mesh_rounds`, the completed-queries history
columns, the metric families — agrees row-exactly with the recorder.

The harness forces the mesh (`mesh_execution=on`) so n=1 also flies:
the single-shard flight is the degenerate baseline the attribution
must still reconcile. Warm runs (second execution, compiles cached)
are the measured ones — cold-run tracing/setup wall that happens
outside the instrumented sites is exactly the unattributed remainder
the recorder reports honestly instead of inventing.
"""
import json
import re
import sys

import pytest

from presto_tpu.exec.failpoints import FAILPOINTS
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs import flight
from presto_tpu.obs.flight import (BUCKETS, FLIGHTS, KIND_BUCKET,
                                   FlightRecorder)
from presto_tpu.obs.metrics import REGISTRY


SF = 0.005

#: TPC-H Q1: scan-heavy grouped
#: aggregation — the per-batch dispatch + partial-state exchange path
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity), "
      "sum(l_extendedprice), avg(l_discount), count(*) from lineitem "
      "where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus order by 1, 2")

#: TPC-DS q27: 5-way star join +
#: ROLLUP partial states crossing the hash exchange
Q27 = ("select i_item_id, s_state, grouping(s_state) g_state, "
       "avg(ss_quantity) agg1, avg(ss_list_price) agg2, "
       "avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4 "
       "from store_sales, customer_demographics, date_dim, store, item "
       "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
       "and ss_store_sk = s_store_sk and ss_cdemo_sk = cd_demo_sk "
       "and cd_gender = 'M' and cd_marital_status = 'S' "
       "and cd_education_status = 'College' and d_year = 2002 "
       "and s_state in ('TN', 'TN', 'TN', 'TN', 'TN', 'TN') "
       "group by rollup (i_item_id, s_state) "
       "order by i_item_id nulls last, s_state nulls last limit 100")


def _props(n, **extra):
    # "on" (not "auto") so the 1-device flight exists too — auto would
    # route n<2 to the single-device path with no recorder
    return {"mesh_execution": "on", "mesh_devices": n, **extra}


@pytest.fixture(scope="module")
def tpch():
    return LocalRunner(tpch_sf=SF, rows_per_batch=1 << 11)


@pytest.fixture(scope="module")
def tpcds():
    return LocalRunner(catalog="tpcds", tpch_sf=SF,
                       rows_per_batch=1 << 11)


def _fly(runner, sql, n, warm=True, **extra):
    """Execute on a forced n-device mesh and return (result, flight).
    ``warm`` pays one untimed run first so compiles are cached and the
    measured flight is the steady-state one."""
    if warm:
        runner.execute(sql, properties=_props(n, **extra))
    before = FLIGHTS.snapshot()
    res = runner.execute(sql, properties=_props(n, **extra))
    after = FLIGHTS.snapshot()
    # identity, not length: the ring holds 32 flights, and a long
    # in-process suite run legitimately arrives here with it full
    assert after and (not before or after[-1] is not before[-1]), \
        "run did not produce a flight"
    return res, after[-1]


# -- attribution reconciliation (the acceptance criterion) --------------------

@pytest.mark.parametrize("n", [1, 2, 4])
def test_q1_reconciles_and_reports_dominant(tpch, n):
    _, fl = _fly(tpch, Q1, n)
    a = fl.attribution
    assert a is not None
    assert a["n_devices"] == n
    assert a["rounds"] > 0
    # buckets reconcile to >= 90% of measured wall on the warm run, OR
    # the unattributed remainder is bounded in ABSOLUTE terms: the
    # fused exchange + cross-query program cache cut q1's warm wall to
    # tens of milliseconds, where the recorder's few ms of per-record
    # host glue (batch iteration, python dispatch) is a large share of
    # a tiny number — the contract that matters is that the glue stays
    # small, not that it shrinks with the wall
    unattributed = a["wall_s"] * (100.0 - a["reconciled_pct"]) / 100.0
    assert a["reconciled_pct"] >= 90.0 or unattributed <= 0.25, a
    assert abs(sum(a["buckets"].values())
               - a["wall_s"] * a["reconciled_pct"] / 100.0) < 0.05 \
        or a["reconciled_pct"] == 100.0
    # dominant bucket reported per (query, n), and it is the max
    assert a["dominant_bucket"] in BUCKETS
    assert a["buckets"][a["dominant_bucket"]] == \
        max(a["buckets"].values())
    # critical path: one entry per shard, slowest shard is the argmax
    cp = a["critical_path"]
    assert len(cp["per_shard_s"]) == n
    assert cp["per_shard_s"][cp["slowest_shard"]] == \
        max(cp["per_shard_s"])
    # per-shard path never exceeds total bucketed wall (rounds gate
    # shards at most fully); each bucket is independently rounded to
    # 6 decimals, so the sum can trail the true wall by half an ULP
    # per bucket — the slack must cover that, not just float noise
    slack = (len(a["buckets"]) + 1) * 5e-7
    assert max(cp["per_shard_s"]) <= sum(a["buckets"].values()) + slack


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 2, 4])
def test_q27_reconciles_and_reports_dominant(tpcds, n):
    # a 5-way join + rollup is minutes of shard_map compiles across
    # the n sweep, so this rides the slow tier.  The fused exchange +
    # program cache cut q27's warm wall ~3x while the per-record host glue (a few ms of python
    # between ~600 records) stayed put, so the share-based floor moves:
    # the contract is 85% reconciled OR the unattributed remainder
    # bounded absolutely at a few ms per record.
    _, fl = _fly(tpcds, Q27, n)
    a = fl.attribution
    assert a["n_devices"] == n
    unattributed = a["wall_s"] * (100.0 - a["reconciled_pct"]) / 100.0
    assert a["reconciled_pct"] >= 85.0 or unattributed <= 3.0, a
    assert a["dominant_bucket"] in BUCKETS
    assert len(a["critical_path"]["per_shard_s"]) == n


# -- round counts vs the exchange's own accounting ----------------------------

#: hash-partitioned join (broadcast suppressed): the shape whose probe
#: stream still crosses the exchange every round — Q1's fused partial
#: states no longer repartition AT ALL, so the exchange-ledger
#: invariants need a join to stay live
QJOIN = ("select c_name, sum(o_totalprice) from customer "
         "join orders on c_custkey = o_custkey "
         "group by 1 order by 2 desc, 1 limit 5")
_QJOIN_PROPS = {"broadcast_join_row_limit": 1}


def test_round_counts_match_exchange_rounds(tpch):
    # pay compiles first
    tpch.execute(QJOIN, properties=_props(4, **_QJOIN_PROPS))
    ship0 = REGISTRY.value("exchange_repartitions_total")
    resplit0 = REGISTRY.value("mesh_repartition_resplit_total")
    _, fl = _fly(tpch, QJOIN, 4, warm=False, **_QJOIN_PROPS)
    shipped = REGISTRY.value("exchange_repartitions_total") - ship0
    resplits = REGISTRY.value("mesh_repartition_resplit_total") \
        - resplit0
    kinds = [r["kind"] for r in fl.records()]
    assert kinds.count("repartition") == int(shipped) > 0
    assert kinds.count("resplit") == int(resplits)
    # round indices are the record sequence, dense from 0
    assert [r["round"] for r in fl.records()] == \
        list(range(len(kinds)))
    # every kind maps onto a declared bucket
    assert all(KIND_BUCKET[k] in BUCKETS for k in kinds)


def test_fused_q1_has_no_exchange_rounds(tpch):
    """The tentpole, observable in the ledger: Q1's stats-bounded
    grouped aggregation rides the fused wave programs and the gathered
    finisher, so NO partial state crosses a repartition round."""
    tpch.execute(Q1, properties=_props(4))
    ship0 = REGISTRY.value("exchange_repartitions_total")
    _, fl = _fly(tpch, Q1, 4, warm=False)
    assert REGISTRY.value("exchange_repartitions_total") == ship0
    kinds = [r["kind"] for r in fl.records()]
    assert kinds.count("repartition") == 0
    assert kinds.count("dispatch") > 0
    # fused multi-round dispatches: device rounds outnumber host records
    a = fl.attribution
    assert a["device_rounds"] >= a["rounds"]


# -- EXPLAIN ANALYZE section vs system.runtime.mesh_rounds --------------------

def test_explain_analyze_matches_system_table(tpch):
    res = tpch.execute("explain analyze " + Q1, properties=_props(2))
    text = "\n".join(r[0] for r in res.rows)
    assert "Mesh rounds:" in text
    assert "Mesh verdict:" in text and "dominates" in text
    fl = FLIGHTS.last()
    m = re.search(r"Mesh rounds: (\d+) rounds on (\d+) devices", text)
    assert m and int(m.group(1)) == fl.attribution["rounds"]
    assert int(m.group(2)) == 2

    # the per-round table in the text, row-exact against the system
    # table (same renderer, obs/flight.round_rows — but prove it
    # end-to-end through SQL)
    rows = tpch.execute(
        "select round, stage, kind, bucket, rows, bytes, loads, rounds "
        "from system.runtime.mesh_rounds "
        f"where query_id = '{fl.query_id}'").rows
    assert len(rows) == fl.attribution["rounds"]
    printed = re.findall(
        r"^\s+(\d+)\s+(-?\d+)\s+(\w+)\s+(\w+)\s+[\d,.]+\s+(\d+)"
        r"\s+(\d+)\s*(\S*)\s+(\d+)\s*$", text, re.M)
    assert len(printed) == len(rows)
    for p, r in zip(printed, rows):
        assert (int(p[0]), int(p[1]), p[2], p[3]) == \
            (r[0], r[1], r[2], r[3])
        assert (int(p[4]), int(p[5])) == (r[4], r[5])
        assert p[6] == (r[6] or "")
        assert int(p[7]) == r[7]    # device rounds inside the dispatch


def test_completed_queries_carries_attribution(tpch):
    _, fl = _fly(tpch, Q1, 2, warm=False)
    # query ids restart per runner instance, so the process-global
    # history can hold same-named records from other suites' runners —
    # our run is the one whose bucket JSON matches the flight exactly
    rows = tpch.execute(
        "select mesh_rounds, mesh_dominant_bucket, mesh_overhead_ms, "
        "mesh_buckets from system.runtime.completed_queries "
        f"where query_id = '{fl.query_id}'").rows
    want = json.dumps(fl.attribution["buckets"], sort_keys=True)
    ours = [r for r in rows if r[3] == want]
    assert len(ours) == 1, rows
    rounds, dominant, overhead_ms, buckets_json = ours[0]
    assert rounds == fl.attribution["rounds"]
    assert dominant == fl.attribution["dominant_bucket"]
    assert overhead_ms == pytest.approx(
        fl.attribution["overhead_s"] * 1e3, abs=0.01)
    assert sorted(json.loads(buckets_json)) == sorted(BUCKETS)
    # non-mesh queries carry the zero/NULL tail, not stale data
    tpch.execute("select 17 * 3")
    rows = tpch.execute(
        "select mesh_rounds, mesh_dominant_bucket from "
        "system.runtime.completed_queries "
        "where query = 'select 17 * 3'").rows
    assert rows[-1][0] == 0 and rows[-1][1] is None


# -- failpoint-injected stall lands in the right bucket -----------------------

def test_injected_repartition_sleep_attributed(tpch):
    # Q1 no longer repartitions at all on the fused plane — the
    # failpoint needs a hash-partitioned join to fire
    _, green = _fly(tpch, QJOIN, 2, **_QJOIN_PROPS)
    # the sleep must dwarf run-to-run ship-wall noise (a warm
    # repartition round drifts by a few hundred ms under load), so the
    # delta assertion below stays deterministic
    FAILPOINTS.configure("mesh.repartition", action="sleep",
                         sleep_s=2.0, times=1)
    try:
        _, red = _fly(tpch, QJOIN, 2, warm=False, **_QJOIN_PROPS)
    finally:
        FAILPOINTS.clear("mesh.repartition")
    assert FAILPOINTS.triggers("mesh.repartition") == 0  # cleared
    g = green.attribution["buckets"]
    r = red.attribution["buckets"]
    # the injected 2s shows up in repartition — not smeared into
    # sync/stall/staging (red/green on the attribution)
    assert r["repartition"] - g["repartition"] >= 1.0, (g, r)
    for other in ("control_sync", "stall", "host_staging"):
        assert r[other] - g[other] < 1.0, (other, g, r)


# -- recording cost: counted, not timed ---------------------------------------

def _calls(fn) -> int:
    """Python and C calls ``fn()`` makes on this thread: the
    recorder's cost as a count, which a loaded host cannot move."""
    n = [0]

    def prof(frame, event, arg):
        if event in ("call", "c_call"):
            n[0] += 1
    sys.setprofile(prof)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return n[0]


def test_recorder_cost_is_constant_a_record(tpch):
    """What "cheap enough to fly every mesh query" stands on, as
    counts (a ratio of two wall clocks fails on a loaded host): a
    record costs a fixed handful of calls however many the flight
    holds, ``finish()`` is linear in them, and a query records a few
    rows a dispatch, never one a row or a lane. Margins: 15 calls a
    record and 9 a record in ``finish()`` today."""
    def one(fl):
        fl.record("dispatch", stage=1, wall=1e-4, rows=10, nbytes=100,
                  loads=(1, 2, 3, 4))
    bench = FlightRecorder("overhead_bench", 4)
    first = _calls(lambda: one(bench))
    for _ in range(5000):
        one(bench)
    assert _calls(lambda: one(bench)) == first <= 20
    small, large = (FlightRecorder(f"finish_{n}", 4) for n in (1, 2))
    for fl, n in ((small, 1000), (large, 2000)):
        for _ in range(n):
            one(fl)
    grown = (_calls(lambda: large.finish(1.0))
             - _calls(lambda: small.finish(1.0)))
    assert grown <= 12 * 1000
    _, fl = _fly(tpch, Q1, 2, warm=False)
    kinds = [r["kind"] for r in fl.records()]
    assert 0 < len(kinds) <= 4 * kinds.count("dispatch")


# -- session property / metric families / cross-surface registries ------------

def test_mesh_flight_off_skips_recording(tpch):
    flights0 = REGISTRY.value("mesh_flight_queries_total")
    last0 = FLIGHTS.last()
    res = tpch.execute(Q1, properties=_props(2, mesh_flight=False))
    assert res.rows
    assert REGISTRY.value("mesh_flight_queries_total") == flights0
    assert FLIGHTS.last() is last0
    # and EXPLAIN ANALYZE shows no mesh section for the off run
    res = tpch.execute("explain analyze " + Q1,
                       properties=_props(2, mesh_flight=False))
    assert "Mesh rounds:" not in "\n".join(r[0] for r in res.rows)


def test_metric_families_populated(tpch):
    _fly(tpch, Q1, 2, warm=False)
    # Q1's fused plane finishes off an all-gather with ZERO exchange
    # rounds, so the repartition family needs a query that actually
    # ships a hash exchange
    _fly(tpch, QJOIN, 2, warm=False, **_QJOIN_PROPS)
    assert REGISTRY.value("mesh_flight_queries_total") > 0
    assert REGISTRY.value("mesh_rounds_total") > 0
    assert REGISTRY.value("mesh_round_seconds.count") > 0
    assert REGISTRY.value("mesh_attr_dispatch_overhead_seconds_total") \
        > 0
    assert REGISTRY.value("mesh_attr_repartition_seconds_total") > 0
    # overhead total = sum of non-compute buckets, monotonic
    assert REGISTRY.value("mesh_flight_overhead_seconds_total") > 0
    for b in BUCKETS:
        name = f"mesh_attr_{b}_seconds_total"
        assert REGISTRY.value(name, default=-1.0) >= 0.0, name


def test_history_fields_shape():
    assert flight.history_fields(None) == {}
    a = {"rounds": 3, "dominant_bucket": "repartition",
         "overhead_s": 0.5,
         "buckets": {b: 0.0 for b in BUCKETS}}
    f = flight.history_fields(a)
    assert f["mesh_rounds"] == 3
    assert f["mesh_dominant_bucket"] == "repartition"
    assert f["mesh_overhead_ms"] == 500.0
    assert sorted(json.loads(f["mesh_buckets"])) == sorted(BUCKETS)
