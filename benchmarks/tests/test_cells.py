"""Each cell end to end on the CPU: the harness past its look for a
chip, the result line's shape, and `correct` coming out false when the
timed path is broken underneath or the reference is given another
binding."""
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import ROOT

CELLS = ("tpch_sf10_q6", "tpch_sf1_q3", "tpch_sf10_q1")


def _run(cell, seed=2_147_483_659, seconds=0.5, trace=False):
    import harness
    line = json.dumps(harness.run_cell(cell, seed, seconds, trace,
                                       time.perf_counter()))
    return json.loads(line)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(small_cell, bench, name):
    out = _run(small_cell(name))
    assert list(out)[-1] == "checked"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    want = {m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(out["device"])


def test_traced_run_reports_the_per_layer_metrics_it_can_read(small_cell):
    out = _run(small_cell("tpch_sf10_q1"), trace=True)
    assert out["correct"] is True
    # no TPU plane in a CPU trace: the device readers return nothing and
    # their metrics are left out, never reported as 0
    assert set(out["metrics"]) == {
        "plan_ms", "protocol_ms", "scan_cache_hit_pct",
        "dispatches_per_query", "compile_s", "compiles_in_window"}
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert out["metrics"]["scan_cache_hit_pct"]["value"] == 100.0
    assert "busy_s" not in out["device"]


def test_traced_run_holds_untraced_queries_before_the_traced(
        small_cell, monkeypatch, capsys):
    """``device_idle_pct`` holds the busy seconds of a traced query
    against the seconds of an untraced one of the same window."""
    import re
    import harness
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)
    out = _run(small_cell("tpch_sf10_q1"), seconds=1.0, trace=True)
    assert out["correct"] is True
    m = re.search(r"\[window\] (\d+) queries .* (\d+) of them under the "
                  r"tracer", capsys.readouterr().out)
    assert 1 <= int(m.group(2)) < int(m.group(1))


def test_the_configurations_session_properties_go_with_every_statement(
        small_cell, monkeypatch):
    from presto_tpu.server.protocol import PrestoTpuServer
    create, seen = PrestoTpuServer.create_query, []

    def recording(self, sql, overrides, *a, **kw):
        seen.append(dict(overrides))
        return create(self, sql, overrides, *a, **kw)
    monkeypatch.setattr(PrestoTpuServer, "create_query", recording)
    cell = small_cell("tpch_sf1_q3")
    out = _run(cell)
    assert len(seen) >= out["attempted"] + 2
    assert all(o == cell.config["session_properties"] for o in seen)
    assert seen[0]["result_cache"] == "false"


def test_the_window_delta_holds_every_counter_of_the_registry(small_cell):
    import harness
    _run(small_cell("tpch_sf10_q1"))
    names = set(harness.counters())
    assert {"scan_cache_hit_total", "jit_cache_invocations_total"} <= names
    assert len(names) > 20


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced(small_cell, monkeypatch,
                                                name):
    from presto_tpu.exec.runner import LocalRunner
    execute = LocalRunner.execute

    def altered(self, sql, *a, **kw):
        res = execute(self, sql, *a, **kw)
        if "lineitem" in sql:
            res.rows = [[v * (1 + 3e-8) if isinstance(v, float) else v
                         for v in row] for row in res.rows]
        return res
    monkeypatch.setattr(LocalRunner, "execute", altered)
    out = _run(small_cell(name))
    assert out["correct"] is False
    c = out["checked"]["double_rel_gap"]
    assert c["value"] > c["limit"]
    assert out["checked"]["exact_cells_wrong"]["value"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_rows_left_out(small_cell, monkeypatch, name):
    from presto_tpu.connectors.tpch import TpchPageSource
    chunks = TpchPageSource.host_chunks

    def every_other(self):
        for i, chunk in enumerate(chunks(self)):
            if self.split.table.table != "lineitem" or i % 2 == 0:
                yield chunk
    monkeypatch.setattr(TpchPageSource, "host_chunks", every_other)
    out = _run(small_cell(name))
    assert out["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_a_wrong_binding_in_the_reference(small_cell, monkeypatch, name):
    cell = small_cell(name)
    reference = cell.template.reference

    def other(data, sf, bindings, *a, **kw):
        return reference(data, sf, bindings[::-1], *a, **kw)
    monkeypatch.setattr(cell.template, "reference", other)
    assert _run(cell)["correct"] is False


def test_a_failed_query_fails_the_run(small_cell, monkeypatch):
    import harness
    cell = small_cell("tpch_sf10_q6")
    query = harness.Door.query
    calls = {"n": 0}

    def flaky(self, sql):
        calls["n"] += 1
        if calls["n"] == 6:     # past the warm-up, inside the window
            return None, "QueryFailed: injected", 0.001
        return query(self, sql)
    monkeypatch.setattr(harness.Door, "query", flaky)
    out = _run(cell)
    assert out["correct"] is False and out["failed"] == 1


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "tpch_sf10_q1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
