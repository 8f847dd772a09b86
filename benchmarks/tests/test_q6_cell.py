"""The ``tpch_sf10_lineitem`` configuration and its cell ``tpch_sf10_q6``:
the files load through ``harness.Cell`` from ``BENCHMARK.json`` itself;
on the CPU at SF0.01 the answers are correct; the float32 control and a
reference with the chip's own fault (the rows whose ``l_discount``
EQUALS the upper bound left out, as the v5e's division made the program
do before literals were folded on the host) both make ``correct``
false; and the cell's two metrics read the compactor's counters and
spans, or nothing where the program has none."""
import json
import time

import numpy as np
import pytest

from conftest import ROOT

import harness
import spantime

CELL = "tpch_sf10_q6"


def test_the_files_load_through_the_harness(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    assert cell.chips == 1 and cell.sf == 10
    assert cell.config["name"] == "tpch_sf10_lineitem"
    assert cell.traffic["template"] == "q6" and cell.traffic["bindings"] == 2
    base = harness.Cell(bench, "tpch_sf10_q1", ROOT).config
    same = ("scale_factor", "catalog", "reference_data",
            "rows_per_batch", "scan_cache_bytes", "session_properties",
            "reduced", "assumed")
    assert all(cell.config[k] == base[k] for k in same)
    assert cell.config["tables"] == {"lineitem": 59987676}
    # tpch_sf10's connector, and a catalog that holds lineitem alone
    assert cell.config["connector"] == dict(
        base["connector"], args=dict(base["connector"]["args"],
                                     tables=["lineitem"]))
    assert cell.config["source"] != base["source"]
    # the guarantee that tpch_sf10.json lacks: why this is a file of its own
    assert "literals" in cell.config["guarantees"]
    assert "literals" not in base["guarantees"]
    assert {k: v for k, v in cell.config["guarantees"].items()
            if k != "literals"} == base["guarantees"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"query_s", "setup_s"}
    layer = {m["name"] for m in cell.metrics("per_layer")}
    assert {"compactions_per_query", "compact_sync_ms",
            "scan_hbm_roofline", "device_idle_pct"} <= layer


def test_the_scan_is_four_columns_of_lineitem(bench):
    cell = harness.Cell(bench, CELL, ROOT)
    roofline = harness._module("metrics", "scan_hbm_roofline")
    assert roofline.scan_bytes(cell.template, cell.config["tables"]) \
        == 59987676 * (4 + 8 + 8 + 8)


def _door(cell):
    """The cell's front door at SF0.01, its connector's other
    arguments as the configuration gives them."""
    conn = harness.Cell(cell.bench, CELL, ROOT).config["connector"]
    return harness.Door(dict(cell.config, connector=dict(
        conn, args=dict(conn["args"], sf=cell.sf))))


def test_the_catalog_holds_lineitem_alone(small_cell):
    door = _door(small_cell(CELL))
    try:
        rows, err, _ = door.query("select count(*) from lineitem")
        assert err is None and rows[0][0] > 0
        rows, err, _ = door.query("show tables")
        assert err is None and [list(r) for r in rows] == [["lineitem"]]
        rows, err, _ = door.query("select count(*) from orders")
        assert rows is None and "orders" in err
    finally:
        door.close()


def test_a_program_without_the_tables_argument_ends_before_set_up(
        small_cell, monkeypatch):
    """The parent of PR 29: its ``TpchConnector`` takes ``sf`` alone, so
    the configuration cannot be built and the run ends non-zero at the
    door, with no query sent and no result line."""
    import presto_tpu.connectors.tpch as tpch

    class ParentsConnector(tpch.TpchConnector):
        def __init__(self, sf: float = 0.01):
            super().__init__(sf)

    monkeypatch.setattr(tpch, "TpchConnector", ParentsConnector)
    with pytest.raises(TypeError, match="tables"):
        _door(small_cell(CELL))


def _run(cell, seed=2_147_483_659, seconds=0.5, trace=False):
    return json.loads(json.dumps(harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter())))


def test_the_cell_is_correct_on_the_cpu(small_cell):
    out = _run(small_cell(CELL))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"query_s", "setup_s"}
    assert out["checked"]["exact_cells_wrong"]["value"] == 0


def test_the_float32_control_makes_it_false(small_cell, monkeypatch):
    cell = small_cell(CELL)
    reference = cell.template.reference
    monkeypatch.setattr(
        cell.template, "reference",
        lambda data, sf, bindings: reference(data, sf, bindings,
                                             np.float32))
    out = _run(cell)
    assert out["correct"] is False
    c = out["checked"]["double_rel_gap"]
    assert c["value"] > c["limit"]


def _without_the_upper_bounds_rows(data, sf, bindings):
    """Q6 as the parent answered it on the chip: ``l_discount <
    D + 0.01`` for ``<=``, because the bound, divided on the device,
    came out an ulp low."""
    def part(li):
        prod = li["l_extendedprice"] * li["l_discount"]
        out = []
        for b in bindings:
            lo = (np.datetime64(b["DATE"]) - np.datetime64("1970-01-01")) \
                .astype(int)
            hi = (np.datetime64(f"{int(b['DATE'][:4]) + 1}-01-01")
                  - np.datetime64("1970-01-01")).astype(int)
            d = int(b["DISCOUNT"][2:])
            m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
                 & (li["l_discount_pct"] >= d - 1)
                 & (li["l_discount_pct"] < d + 1)
                 & (li["l_quantity_int"] < int(b["QUANTITY"])))
            out.append(prod[m].sum())
        return out
    parts = data.map_lineitem(part, sf)
    return [[(float(sum(p[i] for p in parts)),)]
            for i in range(len(bindings))]


def test_a_reference_with_the_chips_fault_makes_it_false(small_cell,
                                                         monkeypatch):
    cell = small_cell(CELL)
    monkeypatch.setattr(cell.template, "reference",
                        _without_the_upper_bounds_rows)
    out = _run(cell)
    assert out["correct"] is False
    c = out["checked"]
    assert (c["double_rel_gap"]["value"] > 0.1
            or c["exact_cells_wrong"]["value"] > 0)


def test_the_faulty_reference_is_the_right_one_but_for_the_bound():
    """With the bound moved back the copy above IS the template's
    reference: the test before it fails for the boundary rows alone."""
    import tpchdata
    q6 = harness._module("templates", "q6")
    b = [{"DATE": "1995-01-01", "DISCOUNT": "0.05", "QUANTITY": "24"}]
    wide = [dict(b[0], DISCOUNT="0.06")]
    faulty = _without_the_upper_bounds_rows(tpchdata, 0.01, wide)[0][0][0]
    right = q6.reference(tpchdata, 0.01, wide)[0][0][0]
    assert faulty < 0.8 * right
    assert _without_the_upper_bounds_rows(tpchdata, 0.01, b)[0][0][0] > 0


def test_a_traced_run_reads_the_compactor(bench):
    """Batches of 2^18 rows at SF0.1 (the compactor looks at none under
    2^17): every batch of the 1.9 % filter's output is shrunk, behind
    one ``compaction-liveness`` sync, and both metrics read it."""
    import presto_tpu  # noqa: F401
    cell = harness.Cell(bench, CELL, ROOT)
    conn = dict(cell.config["connector"], args={"sf": 0.1})
    cell.config = dict(cell.config, scale_factor=0.1, connector=conn,
                       rows_per_batch=1 << 18)
    cell.sf = 0.1
    seen = {}
    counters = harness.counters

    def spy():
        seen["last"] = counters()
        seen.setdefault("first", seen["last"])
        return seen["last"]
    harness.counters, trace_seconds = spy, harness.TRACE_SECONDS
    harness.TRACE_SECONDS = 0.3
    try:
        out = _run(cell, seconds=1.5, trace=True)
    finally:
        harness.counters, harness.TRACE_SECONDS = counters, trace_seconds
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    delta = {k: v - seen["first"].get(k, 0.0)
             for k, v in seen["last"].items()}
    n = out["attempted"]
    batches = delta["global_agg_partials_total"] / n    # one a batch
    assert batches >= 600572 // (1 << 18)   # lineitem's rows at SF0.1
    assert m["compactions_per_query"] == batches
    assert m["compact_sync_ms"] > 0
    assert m["compact_sync_ms"] <= m["device_sync_ms"]
    assert m["scan_cache_hit_pct"] == 100.0
    assert delta["compact_checked_total"] == batches * n
    assert delta["compact_lanes_out_total"] * 8 \
        <= delta["compact_lanes_in_total"]
    assert delta["global_agg_merges_total"] == n
    assert delta["expr_device_constant_total"] == 0
    assert delta.get("agg_step_selected_total", 0) == 0


def _span(name, start, end, trace="t1", **attrs):
    return {"name": name, "traceId": trace, "spanId": f"{name}@{start}",
            "parentId": None, "start": float(start), "end": float(end),
            "attrs": attrs}


#: one untraced query: a filter's pull 2..90 ms that launches the filter
#: (10..14), waits for its liveness (14..44, an eager compact launched
#: and marked meanwhile 20..24) and again for the next batch's (50..60);
#: the answer's fetch 90..98 is another kind of sync
SPANS = [
    _span("query", 0.000, 0.100),
    _span("op:Filter", 0.002, 0.090),
    _span("dispatch", 0.010, 0.014, program="jit_expr_filter_abc123"),
    _span("device-sync", 0.014, 0.044, what="compaction-liveness"),
    _span("dispatch", 0.020, 0.024, program="jit_op_compact"),
    _span("device-sync", 0.050, 0.060, what="compaction-liveness"),
    _span("device-sync", 0.090, 0.098, what="result"),
]
RUN = {"spans": SPANS, "seconds": [0.101, 0.099],
       "untraced_seconds": [0.101],
       "counters": {"compact_checked_total": 4.0,
                    "compact_applied_total": 3.0}}


def test_the_two_metrics_by_hand():
    read = {n: harness._module("metrics", n).read
            for n in ("compactions_per_query", "compact_sync_ms")}
    assert read["compactions_per_query"](RUN) == 1.5
    assert read["compact_sync_ms"](RUN) == pytest.approx((30 - 4) + 10)
    assert read["compact_sync_ms"](RUN) <= 1e3 * spantime.self_seconds(
        SPANS[0], SPANS)["device-sync"]


@pytest.mark.parametrize("name", ["compactions_per_query",
                                  "compact_sync_ms"])
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent under this benchmark: the spans are there, the
    compactor's counters are not."""
    read = harness._module("metrics", name).read
    assert read(dict(RUN, counters={"jit_cache_invocations_total": 4.0})) \
        is None
    # and an untraced run of the change (no spans): the counter reads,
    # the span reader does not
    bare = dict(RUN, spans=[])
    assert (read(bare) is None) == (name == "compact_sync_ms")
