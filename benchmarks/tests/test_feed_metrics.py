"""The five feed readers of PR 37 (``feedtime.py``), each on a recorded
``run`` worked out by hand, and on the parent's spans (no ``wait_s``,
no ``drained``, no ``starved``), where each returns nothing and does
not raise."""
import pytest

import feedtime
import harness

NAMES = ("sync_wait_ms", "sync_fetch_ms", "drains_per_query",
         "starved_launches_per_query", "host_starved_ms")


def _span(name, start, end, trace="t1", **attrs):
    return {"name": name, "traceId": trace, "spanId": f"{name}@{start}",
            "parentId": None, "start": start / 1e3, "end": end / 1e3,
            "attrs": attrs}


def _sync(what, start, end, wait_ms, drained, trace="t1"):
    return _span("device-sync", start, end, trace, what=what,
                 wait_s=wait_ms / 1e3, drained=drained)


def _launch(start, end, starved, trace="t1"):
    return _span("dispatch", start, end, trace, program="jit_op_x",
                 starved=starved)


#: milliseconds. One untraced query of 100 ms and one traced query (left
#: out by the readers). In the first, under one operator:
#: - a launch that finds the device busy (5..10);
#: - a DRAINED sync followed by two launches: 12..30, its value ready at
#:   22, so 8 ms of fetch, then the refill until the first launch ends
#:   at 38 (that launch finds the device drained: inside the stretch,
#:   counted once); the second launch (39..43) finds work queued;
#: - a LATE read that is not drained (45..50, ready at 49): 4 ms of wait
#:   and 1 of fetch, no stretch;
#: - a STARVED launch with no sync before it (60..66): 6 ms of its own;
#: - two drained syncs with no launch between them (70..76 ready at 72,
#:   77..80 ready at 78) and the launch that refills the device (82..86):
#:   their stretches 72..86 and 78..86 OVERLAP and count once, 4 ms of
#:   fetch and 10 of refill;
#: - the answer's fetch (95..99, ready at 95.5), drained, and no launch
#:   after it: 3.5 ms of fetch and the refill to the query's end, 1 ms.
SPANS = [
    _span("query", 0, 100),
    _span("plan", 0, 2),
    _span("op:Aggregation", 2, 95, batches=2),
    _launch(5, 10, False),
    _sync("compaction-liveness", 12, 30, 10, True),
    _launch(34, 38, True),
    _launch(39, 43, False),
    _sync("agg-state-groups", 45, 50, 4, False),
    _launch(60, 66, True),
    _sync("build-summary", 70, 76, 2, True),
    _sync("build-summary", 77, 80, 1, True),
    _launch(82, 86, True),
    _sync("result", 95, 99, 0.5, True),
    # the traced query, slower under the profiler: never read
    _span("query", 200, 400, trace="t2"),
    _sync("result", 210, 390, 100, True, trace="t2"),
    _launch(391, 399, True, trace="t2"),
]

RUN = {"spans": SPANS, "seconds": [0.101, 0.201],
       "untraced_seconds": [0.101], "counters": {}, "trace": {}}

BY_HAND = {
    "sync_wait_ms": 10 + 4 + (2 + 1) + 0.5,
    "sync_fetch_ms": 8 + 1 + (4 + 2) + 3.5,
    "drains_per_query": 4,
    "starved_launches_per_query": 3,
    # fetch while drained 8 + 4 + 3.5, refill 8 + 10 + 1, launches 6
    "host_starved_ms": 15.5 + 19 + 6,
}

TABLE = {"compaction-liveness": [1, 10, 8, 1],
         "agg-state-groups": [1, 4, 1, 0],
         "build-summary": [2, 3, 6, 2],
         "result": [1, 0.5, 3.5, 1]}


def _read(name, run):
    return harness._module("metrics", name).read(dict(run))


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_recorded_run_to_the_value_by_hand(name):
    assert _read(name, RUN) == pytest.approx(BY_HAND[name])


def test_the_table_by_kind_and_the_three_parts():
    f = feedtime.feed_of(SPANS, SPANS[:1])
    assert f["queries"] == 1
    assert {k: pytest.approx(v) for k, v in TABLE.items()} == f["by_what"]
    assert f["starved_ms"] == {"fetch": pytest.approx(15.5),
                               "refill": pytest.approx(19.0),
                               "launch": pytest.approx(6.0)}
    # the traced query's own, for the check beside a kept device trace
    traced = feedtime.feed_of(SPANS, [SPANS[13]])
    assert traced["by_what"] == {"result": [1, pytest.approx(100.0),
                                            pytest.approx(80.0), 1]}
    assert traced["starved_ms"]["fetch"] == pytest.approx(80.0)
    assert traced["starved_ms"]["refill"] == pytest.approx(9.0)
    assert traced["starved_launches"] == 1


def test_wait_and_fetch_add_up_to_the_syncs_self_time():
    self_ms = _read("device_sync_ms", RUN)
    assert self_ms == pytest.approx(18 + 5 + 6 + 3 + 4)
    assert BY_HAND["sync_wait_ms"] + BY_HAND["sync_fetch_ms"] \
        == pytest.approx(self_ms)


def test_a_launch_inside_a_fetch_queues_work_and_is_not_the_fetch():
    """A prefetch thread's launch (26..28) while the first sync fetches:
    the sync's self time loses those 2 ms, and the device has work again
    at 28, so the stretch is 22..28 and the launch at 34 is starved on
    its own."""
    spans = SPANS[:13] + [_launch(26, 28, False)]
    f = feedtime.feed_of(spans, spans[:1])
    assert f["by_what"]["compaction-liveness"] \
        == [1, pytest.approx(10.0), pytest.approx(6.0), 1]
    assert f["starved_ms"] == {"fetch": pytest.approx(15.5 - 2),
                               "refill": pytest.approx(19.0 - 8),
                               "launch": pytest.approx(6.0 + 4)}


def test_a_launch_inside_a_wait_is_not_the_wait():
    """Another thread launches (14..16) while the first sync waits: the
    sync's SELF time splits at the ready instant, 8 ms before it and 8
    after, so wait and fetch still add up to ``device_sync_ms``."""
    run = dict(RUN, spans=SPANS + [_launch(14, 16, False)])
    assert feedtime.feed_of(run["spans"], SPANS[:1])["by_what"][
        "compaction-liveness"] == [1, pytest.approx(8.0),
                                   pytest.approx(8.0), 1]
    assert _read("sync_wait_ms", run) + _read("sync_fetch_ms", run) \
        == pytest.approx(_read("device_sync_ms", run)) \
        == pytest.approx(36.0 - 2.0)
    assert _read("host_starved_ms", run) \
        == pytest.approx(BY_HAND["host_starved_ms"])


def test_unknown_counts_as_not_drained():
    spans = [dict(s, attrs={**s["attrs"], "drained": None})
             if s["name"] == "device-sync" else
             dict(s, attrs={**s["attrs"], "starved": None})
             for s in SPANS]
    run = dict(RUN, spans=spans)
    assert _read("drains_per_query", run) == 0
    assert _read("starved_launches_per_query", run) == 0
    assert _read("host_starved_ms", run) == 0
    assert _read("sync_wait_ms", run) == pytest.approx(17.5)


def test_the_feed_line_is_printed_once_a_run(capsys):
    run = dict(RUN)
    for name in NAMES:
        harness._module("metrics", name).read(run)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[feed]")]
    assert len(lines) == 1
    line = lines[0]
    assert "compaction-liveness x1.00 wait 10.000 fetch 8.000 " \
           "drains 1.00" in line
    assert "host_starved_ms 40.500 = fetch while drained 15.500 + " \
           "refill 19.000 + starved launches 6.000" in line
    assert line.index("compaction-liveness") < line.index("build-summary") \
        < line.index("result")


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_read_nothing(name, capsys):
    """The parent of PR 37 under this benchmark: the same spans without
    ``wait_s``, ``drained`` and ``starved``; and an untraced run."""
    bare = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k not in ("wait_s", "drained", "starved")})
            for s in SPANS]
    assert _read(name, dict(RUN, spans=bare)) is None
    assert _read(name, dict(RUN, spans=[],
                            untraced_seconds=[0.101, 0.201])) is None
    assert "[feed]" not in capsys.readouterr().out


def test_traced_cell_reports_the_five_beside_the_old(small_cell,
                                                     monkeypatch, capsys):
    """A traced run on the CPU, so no number of it is a device's: the
    five are there, wait and fetch add up to ``device_sync_ms``, no more
    launches starve than are made and no more syncs drain than are
    counted, and the line is printed once."""
    import json
    import time
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)
    out = json.loads(json.dumps(harness.run_cell(
        small_cell("tpch_sf10_q6"), 2_147_483_659, 1.0, True,
        time.perf_counter())))
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NAMES) <= set(m)
    assert m["sync_wait_ms"] + m["sync_fetch_ms"] \
        == pytest.approx(m["device_sync_ms"], rel=0.01)
    assert 0 <= m["starved_launches_per_query"] <= m["launches_per_query"]
    assert 1 <= m["drains_per_query"]
    assert 0 < m["host_starved_ms"]
    said = capsys.readouterr().out
    assert said.count("[feed]") == 1
    assert said.index("[feed]") < said.index("[window]")
