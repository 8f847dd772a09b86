"""Device profiling & cost attribution plane (obs/profiler.py).

Covers: executable introspection (compile seconds, cost/memory
analysis, invocation + device-time ledger) through real jit-cache
entries and the `system.runtime.executables` SQL surface; per-operator
device-time attribution and the EXPLAIN ANALYZE Executables/Verdict
sections; HBM gauge sampling with a fake device (XLA:CPU has no
memory_stats); history-sink rotation; and the bench regression gate's smoke mode
(tier-1 keeps the gate itself from rotting).
"""
import json
import os
import subprocess
import sys

import pytest

from presto_tpu import types as T
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs import profiler
from presto_tpu.obs.metrics import REGISTRY, MetricsRegistry
from presto_tpu.obs.profiler import (
    EXECUTABLES, cost_verdict, hbm_totals, operator_scope, profiled,
    sample_hbm,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.01)


def _sql(runner, sql, **kw):
    return runner.execute(sql, **kw).rows


# -- executable registry ------------------------------------------------------

def test_jit_entry_registers_executable():
    import jax.numpy as jnp

    from presto_tpu.batch import Batch
    from presto_tpu.ops.jitcache import compact_jit
    b = Batch.from_pydict({"x": (T.BIGINT, list(range(10)))})
    compact_jit(b, 16)
    rows = {(e["name"], e["static_key"]): e
            for e in EXECUTABLES.snapshot(analyze=False)}
    rec = rows.get(("compact", "(16,)"))
    assert rec is not None
    assert rec["compiles"] >= 1
    assert rec["invocations"] >= 1
    assert rec["compile_seconds"] > 0.0
    del jnp  # imported for parity with sibling tests


def test_executable_cost_and_memory_analysis():
    from presto_tpu.batch import Batch
    from presto_tpu.ops.jitcache import pad_capacity_jit
    b = Batch.from_pydict({"x": (T.BIGINT, list(range(7)))})
    pad_capacity_jit(b, 32)
    rec = next(e for e in EXECUTABLES.snapshot(analyze=True)
               if e["name"] == "pad_capacity")
    # XLA:CPU supports both introspection APIs (conftest pins the
    # backend); bytes move through a pad, flops may legitimately be 0
    assert rec["bytes_accessed"] is not None
    assert rec["bytes_accessed"] > 0
    assert rec["arg_bytes"] is not None and rec["arg_bytes"] > 0
    assert rec["output_bytes"] is not None and rec["output_bytes"] > 0


def test_registry_is_bounded():
    reg = profiler.ExecutableRegistry(max_records=3)
    for i in range(6):
        reg.register("k", (i,))
    assert len(reg.snapshot(analyze=False)) == 3


def test_profiled_call_attributes_to_operator():
    """The contextvar plumbing end to end: a profiled dispatch charges
    the executable AND the operator scope's stats collector."""
    from presto_tpu.batch import Batch
    from presto_tpu.exec.stats import StatsCollector
    from presto_tpu.ops.jitcache import pad_capacity_jit
    b = Batch.from_pydict({"x": (T.BIGINT, list(range(5)))})
    stats = StatsCollector()
    node = object()
    # compile outside the profile context: the first (compiling) call
    # is charged as compile time, never as device time
    pad_capacity_jit(b, 64)
    with profiled(True), operator_scope(stats, node):
        pad_capacity_jit(b, 64)
    dev = stats.device_for(node)
    assert dev is not None
    assert dev["device_time_s"] > 0.0
    assert stats.by_node[node].device_time_s == dev["device_time_s"]
    used = stats.executables_used()
    assert used and used[0]["name"] == "pad_capacity"
    assert used[0]["invocations"] == 1


def test_profile_off_is_off():
    from presto_tpu.batch import Batch
    from presto_tpu.exec.stats import StatsCollector
    from presto_tpu.ops.jitcache import pad_capacity_jit
    b = Batch.from_pydict({"x": (T.BIGINT, list(range(5)))})
    stats = StatsCollector()
    node = object()
    with operator_scope(stats, node):   # no profiled()
        pad_capacity_jit(b, 128)
    assert stats.device_for(node) is None
    assert stats.executables_used() == []


# -- SQL + EXPLAIN ANALYZE surfaces ------------------------------------------

def test_explain_analyze_shows_device_columns_and_verdict(runner):
    rows = _sql(runner, """
        explain analyze
        select o_orderpriority, count(*)
          from orders join lineitem on l_orderkey = o_orderkey
         where l_quantity < 24 group by o_orderpriority""")
    text = "\n".join(r[0] for r in rows)
    assert "[device " in text
    assert "FLOP" in text
    assert "Executables (this query, by device time):" in text
    assert "Verdict: " in text
    assert ("input-bound" in text or "compute-bound" in text
            or "balanced" in text)
    # the join node row (not just the aggregate) carries device truth
    join_line = next(ln for ln in text.split("\n") if "- Join[" in ln)
    assert "[device " in join_line


def test_executables_sql_queryable(runner):
    _sql(runner, "select count(*) from lineitem where l_quantity < 5")
    rows = _sql(runner, """
        select name, compiles, compile_seconds, invocations,
               device_time_s, flops, bytes_accessed, arg_bytes
          from system.runtime.executables
         where invocations > 0 order by compile_seconds desc""")
    assert rows
    names = {r[0] for r in rows}
    assert "global_aggregate" in names or "grouped_aggregate" in names
    top = rows[0]
    assert top[2] > 0.0             # compile_seconds
    assert top[3] >= 1              # invocations
    # at least one executable has cost analysis populated
    assert any(r[5] is not None and r[5] > 0 for r in rows)


def test_operator_stats_history_device_columns(runner):
    _sql(runner,
         "select count(*) from orders where o_custkey > 100",
         properties={"profile": True})
    rows = _sql(runner, """
        select query_id, operator, device_time_s, flops, hbm_bytes
          from system.runtime.operator_stats""")
    assert rows
    # the profiled query charged device time to at least one operator
    assert any(r[2] > 0.0 for r in rows)
    assert any(r[3] > 0.0 for r in rows)


def test_cost_verdict_classification():
    from presto_tpu.connectors.spi import TableHandle
    from presto_tpu.exec.stats import NodeStats, StatsCollector
    from presto_tpu.planner.plan import TableScanNode

    compute_node = object()
    stats = StatsCollector()
    stats.by_node[compute_node] = NodeStats(wall_s=0.1,
                                            device_time_s=1.0)
    v = cost_verdict(stats)
    assert v["verdict"] == "compute-bound"
    assert v["compute_s"] == 1.0

    scan = TableScanNode(fields=(), catalog="tpch",
                         table=TableHandle("tpch", "default", "t"),
                         columns=())
    stats2 = StatsCollector()
    stats2.prefetch_stall_s = 1.0
    stats2.by_node[scan] = NodeStats(wall_s=2.0)      # decode wall
    stats2.by_node[compute_node] = NodeStats(device_time_s=0.5)
    v2 = cost_verdict(stats2)
    assert v2["verdict"] == "input-bound"
    assert v2["input_s"] == pytest.approx(3.0)

    assert cost_verdict(StatsCollector()) is None     # nothing profiled


# -- HBM telemetry ------------------------------------------------------------

class _FakeDevice:
    platform = "tpu"
    id = 0

    def __init__(self, in_use=1 << 30, peak=2 << 30):
        self._in_use, self._peak = in_use, peak

    def memory_stats(self):
        return {"bytes_in_use": self._in_use,
                "peak_bytes_in_use": self._peak,
                "bytes_limit": 16 << 30}


def test_sample_hbm_fake_device_gauges():
    reg = MetricsRegistry()
    docs = sample_hbm([_FakeDevice()], registry=reg)
    assert docs == [{"device": "tpu0", "device_id": 0,
                     "bytes_in_use": 1 << 30,
                     "peak_bytes_in_use": 2 << 30,
                     "bytes_limit": 16 << 30}]
    assert reg.gauge("hbm_in_use_bytes.tpu0").value == float(1 << 30)
    assert reg.gauge("hbm_peak_bytes.tpu0").value == float(2 << 30)


def test_sample_hbm_statless_backend_is_empty():
    class _Cpu:
        platform, id = "cpu", 0

        def memory_stats(self):
            return None
    reg = MetricsRegistry()
    assert sample_hbm([_Cpu()], registry=reg) == []
    totals = hbm_totals([_Cpu()], registry=reg)
    assert totals == {"bytesInUse": 0, "peakBytes": 0, "devices": 0}


def test_worker_info_and_nodes_federation():
    """Heartbeat payload carries the HBM sample; the coordinator's
    federator folds it into system.runtime.nodes and the node-labeled
    scrape series."""
    from presto_tpu.obs.exposition import (
        parse_exposition, render_exposition,
    )
    from presto_tpu.obs.metrics import NodeRegistry
    nodes = NodeRegistry()
    nodes.update("w1", state="ACTIVE", hbm_in_use_bytes=123,
                 hbm_peak_bytes=456)
    nodes.update("w2", state="ACTIVE")   # never reported an HBM sample
    text = render_exposition(registry=MetricsRegistry(), nodes=nodes)
    samples, types = parse_exposition(text)
    assert samples[("node_hbm_in_use_bytes", (("node", "w1"),))] == 123.0
    assert samples[("node_hbm_peak_bytes", (("node", "w1"),))] == 456.0
    assert ("node_hbm_in_use_bytes", (("node", "w2"),)) not in samples
    assert types["node_hbm_in_use_bytes"] == "gauge"


def test_nodes_table_has_hbm_columns(runner):
    rows = _sql(runner, """
        select node_id, hbm_in_use_bytes, hbm_peak_bytes
          from system.runtime.nodes""")
    assert rows
    for _, in_use, peak in rows:
        assert in_use >= 0 and peak >= 0   # CPU backend: zeros


def test_registry_evicts_coldest_and_readmits():
    """The cap drops the least-invoked record, and a dropped record's
    live _TimedEntry readmits it on the next dispatch — hot kernels can
    never go permanently invisible (counts survive)."""
    from presto_tpu.obs.profiler import ExecutableRegistry
    reg = ExecutableRegistry(max_records=2)
    hot = reg.register("hot", (1,))
    hot.invocations = 50
    cold = reg.register("cold", (2,))
    reg.register("newcomer", (3,))          # evicts "cold", not "hot"
    names = {r["name"] for r in reg.snapshot(analyze=False)}
    assert names == {"hot", "newcomer"}
    assert cold.evicted and not hot.evicted
    cold.invocations = 7
    reg.readmit(cold)                        # what a dispatch would do
    assert not cold.evicted
    rows = {r["name"]: r for r in reg.snapshot(analyze=False)}
    assert rows["cold"]["invocations"] == 7  # ledger survived eviction
    assert "hot" in rows
    reg.reset()                              # reset keeps the contract
    assert cold.evicted and hot.evicted


# -- jit compile histogram (satellite) ----------------------------------------

def test_jit_compile_seconds_histogram():
    import jax
    import jax.numpy as jnp

    from presto_tpu.obs.metrics import Histogram
    from presto_tpu.ops.jitcache import _TimedEntry
    h = REGISTRY.histogram("jit_compile_seconds")
    assert isinstance(h, Histogram)
    # a fresh entry guarantees a first-call compile regardless of what
    # the rest of the (single-process) suite compiled before
    entry = _TimedEntry("hist_test_kernel", jax.jit(lambda x: x + 1))
    before = h.count
    entry(jnp.arange(4))
    assert h.count >= before + 1
    # the scrape-compatible running sum is still a counter
    assert REGISTRY.counter("jit_compile_seconds_total").value > 0.0


# -- history rotation (satellite) ---------------------------------------------

def test_history_sink_rotation(tmp_path):
    from presto_tpu.obs.history import QueryHistory
    sink = tmp_path / "history.jsonl"
    h = QueryHistory(max_records=10)
    h.configure(sink_path=str(sink), max_sink_bytes=400)
    dropped = REGISTRY.counter("history_records_dropped_total")
    before = dropped.value
    for i in range(40):
        h.add({"query_id": f"q{i:04d}", "state": "FINISHED",
               "query": "select 1", "elapsed_ms": 1.0})
    assert sink.exists() or (tmp_path / "history.jsonl.1").exists()
    assert (tmp_path / "history.jsonl.1").exists()
    # >= 2 rotations happened at this cap, so the first generation's
    # records were dropped and counted
    assert dropped.value > before
    # every surviving line is valid JSON
    for p in (sink, tmp_path / "history.jsonl.1"):
        if p.exists():
            for line in p.read_text().splitlines():
                json.loads(line)


def test_history_sink_unbounded_when_disabled(tmp_path):
    from presto_tpu.obs.history import QueryHistory
    sink = tmp_path / "h.jsonl"
    h = QueryHistory()
    h.configure(sink_path=str(sink), max_sink_bytes=0)   # 0 = unbounded
    for i in range(50):
        h.add({"query_id": f"q{i}", "pad": "x" * 64})
    assert not (tmp_path / "h.jsonl.1").exists()
    assert len(sink.read_text().splitlines()) == 50


# -- doc drift (satellite) ----------------------------------------------------

def test_metric_doc_drift_check_green():
    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "check_metric_names.py")],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_metric_doc_drift_catches_unknown_doc_name(tmp_path):
    doc = tmp_path / "observability.md"
    doc.write_text("The doc names `totally_fake_metric_total` only.\n")
    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "check_metric_names.py"),
         "--docs", str(doc)],
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "totally_fake_metric_total" in out.stderr
    # the reverse direction fires too: real families are undocumented
    # in this stub doc
    assert "not documented" in out.stderr
