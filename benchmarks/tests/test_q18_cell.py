"""The ``tpch_sf10_custorders`` configuration and its cell
``tpch_sf10_q18``: the files load through ``harness.Cell`` from
``BENCHMARK.json`` itself; on the CPU at SF0.01 the answers are correct;
the float32 control and three faults (a row dropped, two rows swapped, a
``c_name`` off by one customer) make ``correct`` false; the four new
metrics and every new counter and span are read by name; and
``tpchdata_q18``'s ``o_totalprice`` and ``c_name`` equal the
connector's."""
import json
import time

import numpy as np
import pytest

from conftest import ROOT, SMALL_SF

import harness

CELL = "tpch_sf10_q18"
METRICS = ("agg_merges_per_query", "agg_merge_ms", "agg_state_groups",
           "grouped_agg_device_ms")
COUNTERS = ("agg_partials_total", "agg_state_merges_total",
            "agg_state_lanes_merged_total", "agg_state_groups_total",
            "plan_semijoin_pushed_total", "dictionary_unify_total",
            "dictionary_unify_entries_total", "agg_sort_path_selected_total")


def test_the_files_load_through_the_harness(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    assert cell.chips == 1 and cell.sf == 10
    assert cell.config["name"] == "tpch_sf10_custorders"
    assert cell.traffic["template"] == "q18" and cell.traffic["bindings"] == 2
    base = harness.Cell(bench, "tpch_sf10_q1", ROOT).config
    same = ("scale_factor", "catalog", "rows_per_batch", "scan_cache_bytes",
            "session_properties", "reduced", "tables")
    assert all(cell.config[k] == base[k] for k in same)
    assert cell.config["reference_data"] == "tpchdata_q18"
    assert cell.config["connector"] == dict(
        base["connector"], args=dict(
            base["connector"]["args"],
            tables=["customer", "orders", "lineitem"],
            distinct_text=["c_name"]))
    assert cell.config["source"] != base["source"]
    assert {k: v for k, v in cell.config["assumed"].items()
            if k != "quantity"} == base["assumed"]
    assert {k: v for k, v in cell.config["guarantees"].items()
            if k not in ("groups", "order")} == base["guarantees"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"query_s", "setup_s"}
    layer = {m["name"] for m in cell.metrics("per_layer")}
    assert set(METRICS) | {"scan_hbm_roofline", "op_device_ms",
                           "device_idle_pct"} <= layer
    q3 = {m["name"] for m in
          harness.Cell(bench, "tpch_sf1_q3", ROOT).metrics("per_layer")}
    assert set(METRICS) <= q3           # both cells run AggSpillBuffer


def test_the_scans_are_the_residency_the_configuration_states(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    roofline = harness._module("metrics", "scan_hbm_roofline")
    assert roofline.scan_bytes(cell.template, cell.config["tables"]) \
        == 59987676 * 16 + 15000000 * 28 + 1500000 * 12


def test_the_bindings_are_two_of_the_four_quantities(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    for seed in (0, 7, 2_147_483_659, 3_300_000_001):
        b = harness.draw_bindings(cell.template, cell.traffic, seed)
        assert len(b) == 2 and b[0] != b[1]
        assert all(x["QUANTITY"] in (312, 313, 314, 315) for x in b)
        assert "{QUANTITY}" not in cell.template.SQL.format(**b[0])


def _door(cell):
    """The cell's front door at SF0.01, its connector's other
    arguments as the configuration gives them."""
    conn = harness.Cell(cell.bench, CELL, ROOT).config["connector"]
    return harness.Door(dict(cell.config, connector=dict(
        conn, args=dict(conn["args"], sf=cell.sf))))


def test_the_data_module_states_the_connectors_columns(small_cell):
    """``tpchdata_q18``'s two columns against the connector's, every row
    at SF0.01, through the front door."""
    cell = small_cell(CELL)
    door = _door(cell)
    try:
        rows, err, _ = door.query(
            "select o_orderkey, o_totalprice from orders order by 1")
        assert err is None
        od = cell.data.orders(cell.sf, 1, len(rows) + 1)
        assert len(rows) == cell.data.row_counts(cell.sf)["orders"]
        assert [int(r[0]) for r in rows] == od["o_orderkey"].tolist()
        assert [float(r[1]) for r in rows] == od["o_totalprice"].tolist()
        rows, err, _ = door.query(
            "select c_custkey, c_name from customer order by 1")
        assert err is None
        cu = cell.data.customer(cell.sf, 1, len(rows) + 1)
        assert len(rows) == cell.data.row_counts(cell.sf)["customer"]
        assert [r[1] for r in rows] == cu["c_name"].tolist()
        assert cu["c_name"][0] == cell.data.customer_name(1) \
            == "Customer#000000001"
    finally:
        door.close()


#: at SF0.01 no order passes 312 (the largest sum is 30x): the
#: rehearsal draws from thresholds that give many rows and a few
SMALL_QUANTITIES = (200, 250, 270, 280)


@pytest.fixture
def cell(small_cell, monkeypatch):
    """The cell at SF0.01 with its connector's other arguments (the
    data facts) as the configuration gives them."""
    c = small_cell(CELL)
    conn = harness.Cell(c.bench, CELL, ROOT).config["connector"]
    c.config = dict(c.config, connector=dict(
        conn, args=dict(conn["args"], sf=c.sf)))
    monkeypatch.setattr(c.template, "QUANTITIES", SMALL_QUANTITIES)
    return c


def test_a_program_without_the_data_fact_ends_before_set_up(
        small_cell, monkeypatch):
    """The parent of PR 33: its ``TpchConnector`` takes ``sf`` and
    ``tables`` alone, so the configuration cannot be built and the run
    ends non-zero at the door, with no query sent and no result line
    (on the chip its first Q18 at SF10 ended in a segmentation fault)."""
    import presto_tpu.connectors.tpch as tpch

    class ParentsConnector(tpch.TpchConnector):
        def __init__(self, sf: float = 0.01, tables=tpch.TABLES):
            super().__init__(sf, tables)

    monkeypatch.setattr(tpch, "TpchConnector", ParentsConnector)
    with pytest.raises(TypeError, match="distinct_text"):
        _door(small_cell(CELL))


def test_a_text_column_stated_distinct_that_is_not_fails_at_staging():
    from presto_tpu.connectors.tpch import TpchConnector
    with pytest.raises(ValueError, match="c_nam"):
        TpchConnector(sf=SMALL_SF, tables=["customer"],
                      distinct_text=["c_nam"])
    door = harness.Door({
        "connector": {"module": "presto_tpu.connectors.tpch",
                      "class": "TpchConnector",
                      "args": {"sf": SMALL_SF, "tables": ["orders"],
                               "distinct_text": ["o_clerk"]}},
        "catalog": "tpch", "rows_per_batch": 8192,
        "scan_cache_bytes": 1 << 28, "session_properties": {}})
    try:
        rows, err, _ = door.query("select count(distinct o_clerk) "
                                  "from orders")
        assert rows is None and "o_clerk" in err
    finally:
        door.close()


def _run(cell, seed=2_147_483_659, seconds=0.5, trace=False):
    return json.loads(json.dumps(harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter())))


def test_the_cell_is_correct_on_the_cpu(cell):
    out = _run(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"query_s", "setup_s"}
    assert out["checked"]["exact_cells_wrong"]["value"] == 0
    assert out["checked"]["double_rel_gap"]["value"] == 0.0
    want = cell.template.reference(cell.data, cell.sf,
                                   [{"QUANTITY": 200}])[0]
    assert len(want) == 100             # limit binds at 200, not at 250
    assert len(cell.template.reference(
        cell.data, cell.sf, [{"QUANTITY": 250}])[0]) == 50


def test_the_float32_control_makes_it_false(cell, monkeypatch):
    reference = cell.template.reference
    monkeypatch.setattr(
        cell.template, "reference",
        lambda data, sf, bindings: reference(data, sf, bindings,
                                             np.float32))
    out = _run(cell)
    assert out["correct"] is False
    c = out["checked"]["double_rel_gap"]
    assert c["value"] > c["limit"]
    # by ONE of the cell's limits, not by each: the control's keys,
    # dates, names and order are the reference's
    assert out["checked"]["exact_cells_wrong"]["value"] == 0


def _dropped(rows):
    return rows[:1] + rows[2:]


def _swapped(rows):
    return rows[1:2] + rows[:1] + rows[2:]


def _name_off_by_one(rows):
    name, cust = rows[0][0], rows[0][1]
    wrong = "Customer#%09d" % (cust + 1)
    assert wrong != name
    return [(wrong,) + tuple(rows[0][1:])] + rows[1:]


@pytest.mark.parametrize("fault", [_dropped, _swapped, _name_off_by_one])
def test_a_fault_in_the_answer_makes_it_false(cell, monkeypatch, fault):
    reference = cell.template.reference

    def faulty(data, sf, bindings):
        answers = reference(data, sf, bindings)
        assert all(len(a) >= 2 for a in answers)
        return [fault(a) for a in answers]
    monkeypatch.setattr(cell.template, "reference", faulty)
    out = _run(cell)
    assert out["correct"] is False
    assert out["checked"]["exact_cells_wrong"]["value"] > 0


def test_a_traced_run_reads_the_state_and_the_plan(cell):
    """Every new metric, counter and span by name, on a traced run."""
    seen = {}
    counters = harness.counters

    def spy():
        seen["last"] = counters()
        seen.setdefault("first", seen["last"])
        return seen["last"]
    spans = {}
    from presto_tpu.obs.trace import TRACER
    export = TRACER.export

    def keep(*a, **kw):
        spans["all"] = export(*a, **kw)
        return spans["all"]
    harness.counters, trace_seconds = spy, harness.TRACE_SECONDS
    harness.TRACE_SECONDS = 0.3
    TRACER.export = keep
    try:
        out = _run(cell, seconds=1.5, trace=True)
    finally:
        harness.counters, harness.TRACE_SECONDS = counters, trace_seconds
        TRACER.export = export
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    n = out["attempted"]
    delta = {k: v - seen["first"].get(k, 0.0)
             for k, v in seen["last"].items()}
    for name in COUNTERS:
        assert name in seen["last"], name
    # three of the four metrics read on a CPU; the device's needs a
    # device trace with the engine's program names in it
    assert m["agg_merges_per_query"] == delta["agg_state_merges_total"] / n
    assert m["agg_state_groups"] == delta["agg_state_groups_total"] / n
    assert m["agg_state_groups"] >= 15000       # one group an order
    assert m["agg_merge_ms"] > 0
    assert m["agg_merges_per_query"] >= 3
    # 60,175 lines in batches of 8192: eight partials of the subquery
    assert delta["agg_partials_total"] / n >= 8
    assert delta["agg_state_lanes_merged_total"] \
        >= 2 * 4096 * delta["agg_state_merges_total"] / 2
    assert delta["agg_sort_path_selected_total"] > 0
    # c_name's two... one batch at SF0.01: nothing to unify in a warm
    # window, and the plan cache holds the plans
    assert delta["plan_semijoin_pushed_total"] == 0
    assert seen["first"]["plan_semijoin_pushed_total"] >= 2
    merges = [s for s in spans["all"] if s["name"] == "agg-merge"]
    assert merges
    assert all({"lanes_in", "groups_out", "mode"} <= set(s["attrs"])
               for s in merges)
    assert {s["attrs"]["mode"] for s in merges} \
        <= {"network", "sort", "finish"}
    assert any(s["attrs"]["mode"] == "network" for s in merges)
    finished = [s["attrs"]["groups_out"] for s in merges
                if s["attrs"]["mode"] == "finish"]
    assert max(finished) == 15000
    if "grouped_agg_device_ms" in m:
        assert m["grouped_agg_device_ms"] > 0


def _span(name, start, end, trace="t1", **attrs):
    return {"name": name, "traceId": trace, "spanId": f"{name}@{start}",
            "parentId": None, "start": float(start), "end": float(end),
            "attrs": attrs}


#: one untraced query: an aggregation's pull 2..90 ms holding a merge
#: 10..40 (a launch 12..16 and a readback 20..30 inside it) and the
#: finish 50..70 (its readback 52..68)
SPANS = [
    _span("query", 0.000, 0.100),
    _span("op:Aggregation", 0.002, 0.090),
    _span("agg-merge", 0.010, 0.040, lanes_in=8192, groups_out=-1,
          mode="network"),
    _span("dispatch", 0.012, 0.016, program="jit_op_grouped_aggregate_merge"),
    _span("device-sync", 0.020, 0.030, what="agg-state-groups"),
    _span("agg-merge", 0.050, 0.070, lanes_in=8192, groups_out=15000,
          mode="finish"),
    _span("device-sync", 0.052, 0.068, what="agg-state-groups"),
]
RUN = {"spans": SPANS, "seconds": [0.101, 0.099],
       "untraced_seconds": [0.101],
       "counters": {"agg_state_merges_total": 6.0,
                    "agg_state_groups_total": 30000.0},
       "trace": {"queries": 2, "device_ops": [
           ["jit_op_grouped_aggregate(123)", 0.5],
           ["jit_op_grouped_aggregate_merge(456)", 0.25],
           ["jit_op_lookup_join(789)", 2.0]]}}


def test_the_four_metrics_by_hand():
    read = {n: harness._module("metrics", n).read for n in METRICS}
    assert read["agg_merges_per_query"](RUN) == 3.0
    assert read["agg_state_groups"](RUN) == 15000.0
    # self time: the merge's 30 ms less its launch and its readback,
    # the finish's 20 less its readback
    assert read["agg_merge_ms"](RUN) == pytest.approx((30 - 4 - 10)
                                                      + (20 - 16))
    assert read["grouped_agg_device_ms"](RUN) == pytest.approx(375.0)


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_them_reads_nothing(name):
    """The parent under this benchmark: no counter, no ``agg-merge``
    span; its group-by's programs are named all the same."""
    read = harness._module("metrics", name).read
    parent = dict(RUN, counters={"jit_cache_invocations_total": 4.0},
                  spans=[s for s in SPANS if s["name"] != "agg-merge"])
    assert (read(parent) is None) == (name != "grouped_agg_device_ms")
    assert read(dict(RUN, spans=[], trace={})) is None or name in (
        "agg_merges_per_query", "agg_state_groups")
