"""The seven per-layer metrics of PR 26, each on a recorded ``run``
worked out by hand, and on a program that lacks what they read (the
parent of PR 26: no ``dispatch`` span, no expression-program counter,
no named modules), where each returns nothing and does not raise."""
import pytest

import harness
import programnames
import spantime


def _span(name, start, end, trace="t1", **attrs):
    return {"name": name, "traceId": trace, "spanId": f"{name}@{start}",
            "parentId": None, "start": float(start), "end": float(end),
            "attrs": attrs}


#: one untraced query of 100 ms and one traced query (left out by the
#: readers). In the first: plan 0..2; an operator 2..90 that launches
#: two programs (10..20, 30..36) and waits for the device once (40..70),
#: during which an eager launch is marked (45..50: the sync's child);
#: the answer's fetch 90..98.
SPANS = [
    _span("query", 0.000, 0.100),
    _span("plan", 0.000, 0.002),
    _span("op:Aggregation", 0.002, 0.090, batches=2),
    _span("dispatch", 0.010, 0.020, program="jit_op_grouped_aggregate"),
    _span("dispatch", 0.030, 0.036, program="jit_expr_filter_abc123"),
    _span("device-sync", 0.040, 0.070, what="build-summary"),
    _span("dispatch", 0.045, 0.050, program="jit_op_compact"),
    _span("device-sync", 0.090, 0.098, what="result"),
    # the traced query, slower under the profiler
    _span("query", 0.200, 0.400, trace="t2"),
    _span("dispatch", 0.210, 0.390, trace="t2", program="jit_op_x"),
]

RUN = {
    "spans": SPANS, "seconds": [0.101, 0.201],
    "untraced_seconds": [0.101],
    "counters": {"jit_cache_invocations_total": 4.0,
                 "expr_program_invocations_total": 6.0},
    "trace": {"queries": 2, "busy_s": 0.5, "window_s": 1.0,
              "device_ops": [["jit_op_grouped_aggregate(1)", 0.20],
                             ["jit_expr_filter_abc123(2)", 0.12],
                             ["jit_scatter-add(3)", 0.08],
                             ["jit_smap_agg_x(4)", 0.04],
                             ["jit_expr_project_def456(5)", 0.02],
                             ["jit__take(6)", 0.01]]},
}

BY_HAND = {
    "launches_per_query": (4 + 6) / 2,
    "dispatch_host_ms": 10 + 6 + 5,
    "device_sync_ms": (30 - 5) + 8,
    # 100 - plan 2 - dispatch 21 - sync 33
    "executor_self_ms": 44,
    "op_device_ms": 1e3 * (0.20 + 0.04) / 2,
    "expr_device_ms": 1e3 * (0.12 + 0.02) / 2,
    "eager_device_ms": 1e3 * (0.08 + 0.01) / 2,
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_metric_reads_the_recorded_run_to_the_value_by_hand(name):
    value = harness._module("metrics", name).read(RUN)
    assert value == pytest.approx(BY_HAND[name])


def test_the_host_metrics_add_up_to_the_query_span():
    q = SPANS[0]
    parts = spantime.self_seconds(q, SPANS)
    assert sum(parts.values()) == pytest.approx(q["end"] - q["start"])
    plan_ms = harness._module("metrics", "plan_ms").read(
        dict(RUN, spans=SPANS[:8]))
    total = plan_ms + sum(BY_HAND[n] for n in (
        "dispatch_host_ms", "device_sync_ms", "executor_self_ms"))
    assert total == pytest.approx(100.0)


def test_scan_stage_on_a_miss_is_a_class_of_its_own():
    spans = SPANS[:8] + [_span("scan-stage", 0.003, 0.009,
                               table="lineitem")]
    parts = spantime.self_seconds(SPANS[0], spans)
    assert parts["scan-stage"] == pytest.approx(0.006)
    assert parts[spantime.EXECUTOR] == pytest.approx(0.044 - 0.006)


def test_a_compile_inside_a_launch_counts_as_the_launch():
    spans = SPANS[:8] + [_span("compile", 0.012, 0.019, program="jit(x)")]
    assert spantime.self_seconds(SPANS[0], spans)["dispatch"] \
        == pytest.approx(0.021)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    """The parent of PR 26 under this benchmark: ``query``, ``plan``
    and ``op:*`` spans only, ``jit_cache_invocations_total`` only,
    modules named ``jit_run`` and ``jit__lambda_``."""
    old = {
        "spans": [s for s in SPANS
                  if s["name"] in ("query", "plan", "op:Aggregation")],
        "seconds": RUN["seconds"], "untraced_seconds": [0.101],
        "counters": {"jit_cache_invocations_total": 4.0},
        "trace": {"queries": 2, "busy_s": 0.5, "window_s": 1.0,
                  "device_ops": [["jit_run(1)", 0.2],
                                 ["jit__lambda_(2)", 0.1]]},
    }
    assert harness._module("metrics", name).read(old) is None
    # and an untraced run's (no spans, no trace) as well
    bare = dict(old, spans=[], trace={}, untraced_seconds=[0.101, 0.2])
    if name != "launches_per_query":
        assert harness._module("metrics", name).read(
            dict(bare, counters=RUN["counters"])) is None


def test_kind_of_a_program_name():
    assert programnames.kind_of("jit_op_lookup_join(123)") == "op"
    assert programnames.kind_of("jit_smap_agg_x(1)") == "op"
    assert programnames.kind_of("jit_expr_filter_0a1b2c(9)") == "expr"
    for eager in ("jit_scatter-add(1)", "jit__take(2)", "jit_slice(3)",
                  "jit_run(4)", "jit__lambda_(5)"):
        assert programnames.kind_of(eager) == "eager"


def test_traced_cell_reports_the_new_metrics_it_can_read(
        small_cell, monkeypatch):
    """On the CPU (no TPU plane in the trace) the counter and the three
    span readers report, from the window's untraced queries; the three
    device readers report nothing."""
    import json
    import time
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)
    out = json.loads(json.dumps(harness.run_cell(
        small_cell("tpch_sf10_q1"), 2_147_483_659, 1.0, True,
        time.perf_counter())))
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"launches_per_query", "dispatch_host_ms", "device_sync_ms",
            "executor_self_ms"} <= set(m)
    assert not {"op_device_ms", "expr_device_ms",
                "eager_device_ms"} & set(m)
    assert m["launches_per_query"] > m["dispatches_per_query"] > 0
    assert all(m[k] > 0 for k in ("dispatch_host_ms", "device_sync_ms",
                                  "executor_self_ms"))
