"""The ``tpch_sf10_suppwait`` configuration and its cell
``tpch_sf10_q21``: the files load through ``harness.Cell`` from
``BENCHMARK.json`` itself; ``tpchdata_q21``'s columns equal the
connector's, row by row at SF0.01; on the CPU at SF0.01 the answers are
correct; three faults (a row dropped, a count off by one, two rows
swapped) make ``correct`` false; the three new metrics and every new
counter and span are read by name."""
import json
import time

import pytest

from conftest import ROOT, SMALL_SF

import harness

CELL = "tpch_sf10_q21"
METRICS = ("semi_build_ms", "semi_expanded_lanes_per_query",
           "semi_residual_device_ms")
COUNTERS = ("semi_join_residual_total.keyed",
            "semi_join_residual_total.expand",
            "semi_join_expanded_lanes_total",
            "plan_semijoin_summarized_total")

#: at SF0.01 a nation has four of the hundred suppliers: the rehearsal
#: draws from nations whose answers hold several rows
SMALL_NATIONS = ("FRANCE", "UNITED STATES", "INDONESIA", "GERMANY")


def test_the_files_load_through_the_harness(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    assert cell.chips == 1 and cell.sf == 10
    assert cell.config["name"] == "tpch_sf10_suppwait"
    assert cell.traffic["template"] == "q21" and cell.traffic["bindings"] == 2
    base = harness.Cell(bench, "tpch_sf10_q1", ROOT).config
    same = ("scale_factor", "catalog", "rows_per_batch", "scan_cache_bytes",
            "session_properties", "reduced")
    assert all(cell.config[k] == base[k] for k in same)
    assert cell.config["reference_data"] == "tpchdata_q21"
    assert cell.config["connector"]["args"] == {
        "sf": 10, "tables": ["supplier", "nation", "orders", "lineitem"],
        "distinct_text": ["s_name"],
        "clustered_by": {"lineitem": ["l_orderkey"]}}
    assert cell.config["tables"] == {
        "lineitem": 59987676, "orders": 15000000, "supplier": 100000,
        "nation": 25}
    assert cell.config["source"] != base["source"]
    assert {k: v for k, v in cell.config["assumed"].items()
            if k != "nation"} == base["assumed"]
    assert {k: v for k, v in cell.config["guarantees"].items()
            if k not in ("exists", "order")} == base["guarantees"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"query_s", "setup_s"}
    layer = {m["name"] for m in cell.metrics("per_layer")}
    assert set(METRICS) | {"scan_hbm_roofline", "op_device_ms",
                           "device_idle_pct"} <= layer
    q18 = {m["name"] for m in
           harness.Cell(bench, "tpch_sf10_q18", ROOT).metrics("per_layer")}
    assert not set(METRICS) & q18       # this cell's alone
    assert cell.template.KINDS == ("string", "int")
    assert cell.template.LIMIT == 100


def test_the_scans_are_the_residency_the_configuration_states(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    roofline = harness._module("metrics", "scan_hbm_roofline")
    assert roofline.scan_bytes(cell.template, cell.config["tables"]) \
        == 59987676 * 24 + 15000000 * 12 + 100000 * 20 + 25 * 12


def test_the_bindings_are_two_of_the_25_nations(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    assert cell.template.NATIONS == cell.data.NATIONS
    assert len(set(cell.data.NATIONS)) == 25
    for seed in (0, 7, 2_147_483_659, 3_500_000_001):
        b = harness.draw_bindings(cell.template, cell.traffic, seed)
        assert len(b) == 2 and b[0] != b[1]
        assert all(x["NATION"] in cell.data.NATIONS for x in b)
        assert "{NATION}" not in cell.template.SQL.format(**b[0])


def _config(cell):
    """The cell's configuration at SF0.01, its connector's other
    arguments (the tables, the data facts) as the file gives them."""
    conn = harness.Cell(cell.bench, CELL, ROOT).config["connector"]
    return dict(cell.config, connector=dict(
        conn, args=dict(conn["args"], sf=cell.sf)))


def test_the_data_module_states_the_connectors_columns(small_cell):
    """``tpchdata_q21`` against the connector, every row at SF0.01,
    through the front door."""
    cell = small_cell(CELL)
    data, sf = cell.data, cell.sf
    door = harness.Door(_config(cell))

    def column(sql):
        rows, err, _ = door.query(sql)
        assert err is None, err
        return rows
    try:
        n = data.row_counts(sf)
        li = data.lineitem(sf, 1, n["orders"] + 1)
        rows = column("select l_orderkey, l_suppkey, l_commitdate, "
                      "l_receiptdate from lineitem "
                      "order by l_orderkey, l_linenumber")
        assert len(rows) == len(li["l_orderkey"]) == data.lineitem_rows(sf) \
            == int(li["lines"].sum())
        got = list(zip(*rows))
        assert [int(v) for v in got[0]] == li["l_orderkey"].tolist()
        assert [int(v) for v in got[1]] == li["l_suppkey"].tolist()
        for at, name in ((2, "l_commitdate"), (3, "l_receiptdate")):
            assert [harness._cell("date", v) for v in got[at]] \
                == li[name].tolist()
        assert li["lines"].max() == data.MAX_LINES and li["lines"].min() == 1
        od = data.orders(sf, 1, n["orders"] + 1)
        rows = column("select o_orderkey, o_orderstatus from orders "
                      "order by 1")
        assert [int(r[0]) for r in rows] == od["o_orderkey"].tolist()
        assert [r[1] for r in rows] == [
            data.ORDER_STATUS[c] for c in od["o_orderstatus"]]
        assert {r[1] for r in rows} == set(data.ORDER_STATUS)
        su = data.supplier(sf, 1, n["supplier"] + 1)
        rows = column("select s_suppkey, s_name, s_nationkey from supplier "
                      "order by 1")
        assert len(rows) == n["supplier"] == 100
        assert [int(r[0]) for r in rows] == su["s_suppkey"].tolist()
        assert [r[1] for r in rows] == su["s_name"].tolist()
        assert [int(r[2]) for r in rows] == su["s_nationkey"].tolist()
        assert su["s_name"][0] == data.supplier_name(1) \
            == "Supplier#000000001"
        na = data.nation()
        rows = column("select n_nationkey, n_name from nation order by 1")
        assert [int(r[0]) for r in rows] == na["n_nationkey"].tolist()
        assert [r[1] for r in rows] == na["n_name"].tolist()
    finally:
        door.close()


def test_a_program_without_the_data_fact_ends_before_set_up(
        small_cell, monkeypatch):
    """The parent of PR 35: its ``TpchConnector`` takes ``sf``,
    ``tables`` and ``distinct_text``, so the configuration cannot be
    built and the run ends non-zero at the door, with no query sent and
    no result line (PERF.md section 4 has what it did with the same
    tables on the chip)."""
    import presto_tpu.connectors.tpch as tpch

    class ParentsConnector(tpch.TpchConnector):
        def __init__(self, sf: float = 0.01, tables=tpch.TABLES,
                     distinct_text=()):
            super().__init__(sf, tables, distinct_text)

    monkeypatch.setattr(tpch, "TpchConnector", ParentsConnector)
    with pytest.raises(TypeError, match="clustered_by"):
        harness.Door(_config(small_cell(CELL)))


def test_the_summaries_read_the_fact(small_cell):
    """``clustered_by`` reaches the plan: both summaries by order key
    are planned over an input in the key's order."""
    cell = small_cell(CELL)
    door = harness.Door(_config(cell))
    try:
        rows, err, _ = door.query(
            "explain " + cell.template.SQL.format(NATION="FRANCE"))
        assert err is None
        text = "\n".join(r[0] for r in rows)
        summaries = [ln for ln in text.splitlines() if "$semi_min" in ln]
        assert len(summaries) == 2
        assert all("ordered input" in ln for ln in summaries)
    finally:
        door.close()


@pytest.fixture
def cell(small_cell, monkeypatch):
    """The cell at SF0.01, lineitem's 60,472 lines in ONE batch: the
    summaries' states then merge nowhere, and the merge network of their
    five-column state (20 s a capacity to compile on this CPU, three
    capacities with 8192-row batches) is `test_q18_cell.py`'s to
    rehearse, not this file's."""
    c = small_cell(CELL)
    c.config = dict(_config(c), rows_per_batch=65536)
    monkeypatch.setattr(c.template, "NATIONS", SMALL_NATIONS)
    return c


def _run(cell, seed=2_147_483_659, seconds=1.0, trace=False):
    return json.loads(json.dumps(harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter())))


def test_the_cell_is_correct_on_the_cpu(cell):
    out = _run(cell)
    assert out["correct"] is True and out["failed"] == 0
    # (one query at the least: under tier-1's six workers a query of
    # 0.4 s can take longer than the window)
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"query_s", "setup_s"}
    assert out["checked"]["exact_cells_wrong"]["value"] == 0
    assert out["checked"]["double_rel_gap"] == {"value": 0.0, "limit": 0.0}
    answers = cell.template.reference(
        cell.data, cell.sf, [{"NATION": n} for n in SMALL_NATIONS])
    assert all(len(a) >= 2 for a in answers)
    for a in answers:       # numwait descending, then the name
        assert a == sorted(a, key=lambda r: (-r[1], r[0]))


def _dropped(rows):
    return rows[:1] + rows[2:]


def _count_off_by_one(rows):
    return [(rows[0][0], rows[0][1] + 1)] + rows[1:]


def _swapped(rows):
    return rows[1:2] + rows[:1] + rows[2:]


@pytest.mark.parametrize("fault", [_dropped, _count_off_by_one, _swapped])
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
    assert out["checked"]["queries_failed"]["value"] == 0


def test_a_traced_run_reads_the_summaries_and_the_plan(cell):
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
    # both subqueries read a summary by key, every query; no expansion
    assert delta["semi_join_residual_total.keyed"] == 2 * n
    assert delta["semi_join_residual_total.expand"] == 0
    assert m["semi_expanded_lanes_per_query"] == 0
    # a warm window plans nothing (the plan cache holds the plans)
    assert delta["plan_semijoin_summarized_total"] == 0
    assert seen["first"]["plan_semijoin_summarized_total"] >= 2
    assert m["semi_build_ms"] > 0
    assert m["scan_cache_hit_pct"] == 100
    builds = [s for s in spans["all"] if s["name"] == "semi-build"]
    assert len(builds) == 2 * n
    assert all(s["attrs"]["form"] == "keyed" and s["attrs"]["rows_in"] == -1
               for s in builds)
    # one summary over every order, one over those with a late line
    groups = sorted({s["attrs"]["groups_out"] for s in builds})
    assert len(groups) == 2 and groups[1] == 15000 > groups[0] > 10000
    if "semi_residual_device_ms" in m:
        assert m["semi_residual_device_ms"] > 0


def _span(name, start, end, trace="t1", **attrs):
    return {"name": name, "traceId": trace, "spanId": f"{name}@{start}",
            "parentId": None, "start": float(start), "end": float(end),
            "attrs": attrs}


#: one untraced query: two builds, 10..40 ms (a launch and a readback
#: inside it: the build's own cost) and 50..70 ms, and one of another
#: trace
SPANS = [
    _span("query", 0.000, 0.100),
    _span("op:SemiJoin", 0.002, 0.090),
    _span("semi-build", 0.010, 0.040, form="keyed", rows_in=-1,
          groups_out=15000),
    _span("dispatch", 0.012, 0.016, program="jit_op_grouped_aggregate"),
    _span("device-sync", 0.020, 0.030, what="build-summary"),
    _span("semi-build", 0.050, 0.070, form="keyed", rows_in=-1,
          groups_out=13730),
    _span("semi-build", 0.050, 0.070, trace="t2", form="expand",
          rows_in=9, groups_out=-1),
]
RUN = {"spans": SPANS, "seconds": [0.101, 0.099],
       "untraced_seconds": [0.101],
       "counters": {"semi_join_expanded_lanes_total": 4096.0},
       "trace": {"queries": 2, "device_ops": [
           ["jit_expr_semi_keyed_0a1b2c(123)", 0.5],
           ["jit_op_pack_sorted_payload(45)", 0.25],
           ["jit_expr_semi_expand_ffffff(6)", 0.125],
           ["jit_op_grouped_aggregate_merge(456)", 2.0],
           ["jit_expr_filter_0a1b2c(9)", 1.0]]}}


def test_the_three_metrics_by_hand():
    read = {n: harness._module("metrics", n).read for n in METRICS}
    # inclusive of the launch and the readback inside the first build
    assert read["semi_build_ms"](RUN) == pytest.approx(30 + 20)
    assert read["semi_expanded_lanes_per_query"](RUN) == 2048.0
    assert read["semi_residual_device_ms"](RUN) == pytest.approx(437.5)


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_them_reads_nothing(name):
    """The parent under this benchmark: no counter, no ``semi-build``
    span, no such program among the device's rows."""
    read = harness._module("metrics", name).read
    parent = dict(
        RUN, counters={"jit_cache_invocations_total": 4.0},
        spans=[s for s in SPANS if s["name"] != "semi-build"],
        trace={"queries": 2, "device_ops": [
            ["jit_op_semi_join_mask(1)", 1.0]]})
    assert read(parent) is None
    assert read(dict(RUN, spans=[], trace={}, counters={})) is None
