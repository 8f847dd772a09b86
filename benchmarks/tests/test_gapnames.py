"""``gapnames.py``: idle gaps named by the innermost span of the program,
on intervals by hand and on a small trace recorded on the CPU with the
engine's tracer on (``engine_cpu.xplane.pb``: two annotated queries, a
filter, a projection and a group-by over lineitem at SF0.01 in
32768-row batches through ``LocalRunner``, ``jax.profiler`` with the
Python tracer off; PR 26). A CPU trace has no device plane: the
executor threads' ``ThunkExecutor::Execute`` events stand for the
device's operations, and nothing here is a device number."""
import os

import pytest

from conftest import HERE

import gapnames as G


def test_name_gaps_by_hand():
    s = 1e9
    busy = [[1 * s, 2 * s], [5 * s, 6 * s], [12 * s, 13 * s]]
    marks = [(0, 8 * s), (10 * s, 14 * s)]
    spans = [(0.5 * s, 7.5 * s, "query"),
             (0.6 * s, 7.2 * s, "op:Aggregation"),
             (2.5 * s, 4.5 * s, "dispatch[jit_op_x]"),
             (6.2 * s, 6.9 * s, "device-sync[result]"),
             (10.5 * s, 13.5 * s, "query")]
    named = G.name_gaps(busy, marks, spans)
    # gaps: 0..1 (mid 0.5: query starts there), 2..5 (mid 3.5: the
    # dispatch), 6..8 (mid 7: the operator and the query cover it, the
    # sync is over), 10..12 (mid 11: query), 13..14 (mid 13.5: query)
    assert named == [(3.0, "dispatch[jit_op_x]"), (2.0, "query"),
                     (2.0, "op:Aggregation"), (1.0, "query"),
                     (1.0, "query")]
    out = G.summary(named)
    assert out["idle_s"] == 9.0 and out["gaps"] == 5
    assert out["named_share"] == 1.0
    assert out["by_span"][0] == ("query", 4.0)
    assert ("dispatch", 3.0) in out["by_span"]


def test_a_gap_no_span_covers_is_the_protocols():
    s = 1e9
    named = G.name_gaps([[4 * s, 5 * s]], [(0, 10 * s)],
                        [(3 * s, 6 * s, "query")])
    assert named == [(5.0, G.OUTSIDE), (4.0, G.OUTSIDE)]
    assert G.summary(named)["named_share"] == 0.0


def test_label_takes_the_argument_that_tells_spans_apart():
    assert G.label("dispatch", {"program": "jit_op_x"}) \
        == "dispatch[jit_op_x]"
    assert G.label("device-sync", {"what": "result"}) \
        == "device-sync[result]"
    assert G.label("op:Project", {"batches": "3"}) == "op:Project"


def test_recorded_cpu_trace_with_the_engines_annotations(capsys):
    path = os.path.join(HERE, "engine_cpu.xplane.pb")
    busy, marks, spans, plane = G.read_trace(path)
    assert len(marks) == 2 and "CPU" in plane
    names = {lab.split("[")[0] for _, _, lab in spans}
    assert {"query", "plan", "dispatch", "device-sync", "op:Aggregation",
            "op:Project", "op:Filter", "op:TableScan"} <= names
    assert any(lab.startswith("dispatch[jit_expr_filter_")
               for _, _, lab in spans)
    assert any(lab.startswith("dispatch[jit_op_grouped_aggregate")
               for _, _, lab in spans)
    out = G.summary(G.name_gaps(busy, marks, spans))
    assert out["gaps"] >= 4
    assert out["named_share"] >= 0.9
    assert {"dispatch", "device-sync"} <= {n for n, _ in out["by_span"]}
    assert G.main([path]) == 0
    printed = capsys.readouterr().out
    assert "idle seconds by span:" in printed
    assert "the ten longest gaps:" in printed


def test_main_refuses_a_trace_without_marks():
    # the TPU fixture of PR 25 holds marks and device ops but none of
    # the program's annotations: every gap is outside its spans
    busy, marks, spans, plane = G.read_trace(
        os.path.join(HERE, "small.xplane.pb"))
    assert plane.startswith("/device:TPU:0 (busy") and len(marks) == 3
    assert spans == []
    out = G.summary(G.name_gaps(busy, marks, spans))
    assert out["named_share"] == 0.0
    assert G.main([]) == 2
