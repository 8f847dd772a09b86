"""Observability: span tracing, metrics registry, system.runtime SQL,
Chrome-trace export.

Covers the obs/ subsystem end to end: span nesting + distributed
stitching across a real ClusterRunner (coordinator + worker spans share
one trace with consistent query/stage/task ids), metrics counters after
TPC-H-shaped runs, the system.runtime.{queries,tasks,metrics} tables,
and Chrome-trace JSON schema validity.
"""
import json

import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY, TASKS, MetricsRegistry, \
    attach_event_listeners
from presto_tpu.obs.trace import NOOP_SPAN, TRACER, Tracer, chrome_trace, \
    write_chrome_trace


@pytest.fixture
def tracing():
    """Enable the global tracer for one test, restore after."""
    was = TRACER.enabled
    TRACER.enable(True)
    yield TRACER
    TRACER.enable(was)


# -- tracer core -------------------------------------------------------------

def test_disabled_tracer_is_noop():
    t = Tracer(node="t0")
    assert t.enabled is False
    s = t.span("anything", x=1)
    assert s is NOOP_SPAN
    with s:
        pass
    assert t.export() == []
    assert t.context() is None


def test_span_nesting_and_context():
    t = Tracer(node="t1")
    t.enable(True)
    with t.span("query", query_id="q1") as q:
        ctx = t.context()
        assert ctx == {"traceId": q.trace_id, "spanId": q.span_id}
        with t.span("plan"):
            pass
        with t.span("stage", stage_id=0) as st:
            assert st.parent_id == q.span_id
    spans = t.export()
    assert [s["name"] for s in spans] == ["plan", "stage", "query"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["plan"]["parentId"] == by_name["query"]["spanId"]
    assert len({s["traceId"] for s in spans}) == 1
    assert all(s["end"] >= s["start"] for s in spans)


def test_task_span_stitches_wire_context():
    t = Tracer(node="t2")
    t.enable(True)
    with t.span("query") as q:
        ctx = t.context()
    with t.task_span(ctx, "task", task_id="q.0.0"):
        pass
    spans = {s["name"]: s for s in t.export()}
    assert spans["task"]["traceId"] == q.trace_id
    assert spans["task"]["parentId"] == q.span_id


def test_import_spans_dedupes():
    t = Tracer(node="t3")
    t.enable(True)
    with t.span("a"):
        pass
    spans = t.export()
    assert t.import_spans(spans) == 0          # already present
    foreign = dict(spans[0], spanId="other.1", name="b")
    assert t.import_spans([foreign]) == 1
    assert len(t.export()) == 2


def test_wrap_iter_records_batches():
    t = Tracer(node="t4")
    t.enable(True)
    out = list(t.wrap_iter("op:Scan", iter([1, 2, 3])))
    assert out == [1, 2, 3]
    (span,) = t.export()
    assert span["name"] == "op:Scan"
    assert span["attrs"]["batches"] == 3


# -- metrics registry --------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    reg.counter("c_total").inc(2)
    reg.gauge("g").max_update(5)
    reg.gauge("g").max_update(3)          # high-water keeps 5
    reg.histogram("h").observe(1.0)
    reg.histogram("h").observe(3.0)
    rows = {r["name"]: r for r in reg.snapshot()}
    assert rows["c_total"]["value"] == 3
    assert rows["g"]["value"] == 5
    assert rows["h.count"]["value"] == 2
    assert rows["h.sum"]["value"] == 4.0
    assert rows["h.min"]["value"] == 1.0
    assert rows["h.max"]["value"] == 3.0


def test_event_listener_sink():
    from presto_tpu.events import (EventListenerManager,
                                   SplitCompletedEvent, completed_event)
    import time as _t
    reg = MetricsRegistry()
    ev = EventListenerManager()
    attach_event_listeners(ev, reg)
    ev.query_completed(completed_event(
        "q1", "select 1", "u", "FINISHED", _t.perf_counter()))
    ev.split_completed(SplitCompletedEvent("q1", "t", 0, 1.5, 4))
    rows = {r["name"]: r["value"] for r in reg.snapshot()}
    assert rows["queries_finished_total"] == 1
    assert rows["splits_completed_total"] == 1
    assert rows["split_batches_total"] == 4


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.001)


def test_metrics_after_query(runner):
    before = {r["name"]: r["value"] for r in REGISTRY.snapshot()}
    runner.execute(
        "select l_returnflag, sum(l_quantity) from lineitem "
        "group by l_returnflag")
    after = {r["name"]: r["value"] for r in REGISTRY.snapshot()}
    assert after["queries_started_total"] > \
        before.get("queries_started_total", 0)
    assert after["queries_finished_total"] > \
        before.get("queries_finished_total", 0)
    assert after.get("operator_batches_total.tablescan", 0) > \
        before.get("operator_batches_total.tablescan", 0)
    assert after.get("scheduler_quanta_total", 0) > \
        before.get("scheduler_quanta_total", 0)


def test_system_runtime_queries_group_by_state(runner):
    runner.execute("select 1")
    res = runner.execute(
        "select state, count(*) from system.runtime.queries "
        "group by state")
    states = {r[0]: r[1] for r in res.rows}
    assert states.get("FINISHED", 0) >= 1
    assert "RUNNING" in states              # the in-flight query itself


def test_system_runtime_queries_user_and_error(runner):
    runner.execute("select 2", user="alice")
    with pytest.raises(Exception):
        runner.execute("select nope from nation", user="bob")
    res = runner.execute(
        "select query, user, error from system.runtime.queries")
    by_query = {r[0]: (r[1], r[2]) for r in res.rows}
    assert by_query["select 2"][0] == "alice"
    assert by_query["select nope from nation"][0] == "bob"
    assert by_query["select nope from nation"][1]   # error populated


def test_system_runtime_metrics_table(runner):
    runner.execute("select count(*) from nation")
    res = runner.execute(
        "select name, kind, value from system.runtime.metrics "
        "where name = 'queries_started_total'")
    assert len(res.rows) == 1
    name, kind, value = res.rows[0]
    assert kind == "counter" and value >= 1


def test_system_table_count_star_matches_count_col(runner):
    """count(*) over a system table prunes every column, leaving the
    connector page source with nothing to ship — the batch must still
    carry the row count. Regression: count(*) returned 0 while
    count(col) was correct."""
    runner.execute("select count(*) from nation")     # populate metrics
    for table, col in [("system.runtime.metrics", "name"),
                       ("system.runtime.mesh_rounds", "query_id")]:
        star = runner.execute(
            f"select count(*) from {table}").rows[0][0]
        by_col = runner.execute(
            f"select count({col}) from {table}").rows[0][0]
        assert star == by_col, (table, star, by_col)
        if table == "system.runtime.metrics":
            assert star > 0


def test_query_span_tree(runner, tracing):
    runner.execute("select count(*) from nation")
    spans = TRACER.export()
    queries = [s for s in spans if s["name"] == "query"]
    assert queries, "query span missing"
    q = queries[-1]
    tree = [s for s in spans if s["traceId"] == q["traceId"]]
    names = {s["name"] for s in tree}
    assert "plan" in names
    assert any(n.startswith("op:") for n in names)
    ids = {s["spanId"] for s in tree}
    assert all(s["parentId"] in ids for s in tree
               if s["parentId"] is not None)


def test_explain_analyze_trace_section(runner, tracing):
    res = runner.execute("explain analyze select count(*) from nation")
    text = "\n".join(r[0] for r in res.rows)
    assert "Trace (spans by name):" in text
    assert "op:" in text


def test_explain_analyze_no_trace_section_when_disabled(runner):
    assert not TRACER.enabled
    res = runner.execute("explain analyze select count(*) from nation")
    text = "\n".join(r[0] for r in res.rows)
    assert "Trace (spans by name):" not in text


# -- distributed stitching ---------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.server.worker import WorkerServer
    workers = [WorkerServer(tpch_sf=0.001) for _ in range(2)]
    for w in workers:
        w.start()
    urls = [f"http://127.0.0.1:{w.port}" for w in workers]
    runner = ClusterRunner(urls, tpch_sf=0.001, heartbeat=False)
    yield runner, workers
    for w in workers:
        w.stop()


def test_distributed_trace_stitches(cluster, tracing):
    runner, workers = cluster
    res = runner.execute(
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag order by l_returnflag")
    assert len(res.rows) == 3
    spans = TRACER.export()
    q = [s for s in spans if s["name"] == "query"][-1]
    tree = [s for s in spans if s["traceId"] == q["traceId"]]
    qid = q["attrs"]["query_id"]
    stages = [s for s in tree if s["name"] == "stage"]
    tasks = [s for s in tree if s["name"] == "task"]
    assert stages and tasks
    # consistent ids: every stage/task span carries the query id, task
    # ids embed it, and parent links resolve within the trace
    assert all(s["attrs"]["query_id"] == qid for s in stages + tasks)
    assert all(s["attrs"]["task_id"].startswith(qid + ".")
               for s in tasks)
    stage_ids = {s["attrs"]["stage_id"] for s in stages}
    assert {t["attrs"]["stage_id"] for t in tasks} <= stage_ids
    ids = {s["spanId"] for s in tree}
    assert all(s["parentId"] in ids for s in tree
               if s["parentId"] is not None)
    # both workers contributed spans
    nodes = {t["attrs"]["node_id"] for t in tasks}
    assert len(nodes) == 2
    # worker-side operator spans rode along (in-process workers share
    # the ring; cross-process they arrive via the span harvest)
    assert any(s["name"].startswith("op:") for s in tree)


def test_system_runtime_tasks_after_cluster_query(cluster):
    runner, _ = cluster
    runner.execute("select count(*) from nation")
    rows = TASKS.snapshot()
    assert rows, "task registry empty after cluster query"
    assert all(t["state"] in ("PLANNED", "RUNNING", "FINISHED",
                              "FAILED", "ABORTED") for t in rows)
    res = runner.local.execute(
        "select task_id, query_id, state from system.runtime.tasks "
        "where state = 'FINISHED'")
    assert res.rows
    tid, qid, _ = res.rows[0]
    assert tid.startswith(qid + ".")


# -- Chrome-trace export -----------------------------------------------------

def test_chrome_trace_schema(tmp_path, tracing):
    TRACER.clear()
    with TRACER.span("query", query_id="qx") as q:
        with TRACER.span("op:Scan"):
            pass
    path = write_chrome_trace(
        str(tmp_path / "spans.json"), TRACER.export(q.trace_id))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and ms
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["traceId"] == q.trace_id
    # parent/child linkage preserved in args
    by_name = {e["name"]: e for e in xs}
    assert by_name["op:Scan"]["args"]["parentId"] == \
        by_name["query"]["args"]["spanId"]


def test_chrome_trace_empty():
    assert chrome_trace([]) == {"traceEvents": [],
                                "displayTimeUnit": "ms"}


def test_cli_trace_out(tmp_path):
    from presto_tpu.cli import main
    out = tmp_path / "cli_spans.json"
    rc = main(["--execute", "select count(*) from nation",
               "--sf", "0.001", "--trace-out", str(out)])
    try:
        assert rc == 0
        doc = json.load(open(out))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "query" in names
    finally:
        TRACER.enable(False)
