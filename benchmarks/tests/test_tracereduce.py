"""The reduction from a profiler trace to busy time, idle share, top
programs and idle gaps: on intervals by hand, and on a small trace
recorded on the TPU v5e (``small.xplane.pb``: three annotated "queries"
of four dispatches of one jitted f64 step each, 50 ms apart; my chip
run, PR 25)."""
import os

import pytest

from conftest import HERE

import tracereduce as T


def test_union_clip_gaps():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert T.clip([[1, 4], [5, 8]], 2, 6) == [[2, 4], [5, 6]]
    assert T.gaps([[2, 4], [5, 6]], 0, 10) == [[0, 2], [4, 5], [6, 10]]
    assert T.gaps([], 0, 1) == [[0, 1]]


def test_reduce_events_by_hand():
    s = 1e9
    out = T.reduce_events(
        {"/device:TPU:0": [(0 * s, 1 * s), (0.5 * s, 2 * s), (4 * s, 5 * s),
                           (9 * s, 11 * s)]},
        {"/device:TPU:0": [("jit_a(1)", 0, 2 * s), ("jit_b(2)", 4 * s, 5 * s),
                           ("jit_a(1)", 9 * s, 11 * s)]},
        [(0, 3 * s), (4 * s, 10 * s)])
    assert out["window_s"] == 10 and out["queries"] == 2
    assert out["busy_s"] == pytest.approx(2 + 1 + 1)
    assert out["device_ops"] == [["jit_a(1)", 3.0], ["jit_b(2)", 1.0]]
    assert out["idle_gaps"][0] == ["query in flight", 4.0]
    assert ["between queries", 1.0] in out["idle_gaps"] \
        or ["query in flight", 2.0] in out["idle_gaps"]
    assert T.reduce_events({}, {}, []) == {}


def test_recorded_tpu_trace():
    out = T.reduce_trace(os.path.join(HERE, "small.xplane.pb"))
    assert out["queries"] == 3
    assert 0.10 < out["window_s"] < 0.12
    # twelve dispatches of ~11 us each
    assert 1.0e-4 < out["busy_s"] < 2.0e-4
    assert out["device_ops"][0][0].startswith("jit_step(")
    assert out["idle_gaps"][0][0] == "between queries"
    assert 0.05 < out["idle_gaps"][0][1] < 0.06
    idle = 1 - out["busy_s"] / out["window_s"]
    assert 0.99 < idle < 1
