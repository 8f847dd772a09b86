"""Self time of the program's spans, for the three span readers
(``metrics/dispatch_host_ms.py``, ``device_sync_ms.py``,
``executor_self_ms.py``).

A query's spans nest: ``query`` holds ``plan`` and the ``op:*``
operators, an operator's pull holds the ``dispatch`` of each program it
launches and the ``device-sync`` of each value it reads back, and an
eager launch made while a sync waits is the sync's child. A span's SELF
time is its duration less what its children cover, so every instant of
the ``query`` span belongs to exactly one span, the innermost one that
covers it (of spans that overlap without nesting, a prefetch thread's
beside the executor's, the one that started last), and the classes
below add up to the ``query`` span with nothing counted twice.

The readers take the window's queries from BEFORE the profiler started
(the first ``len(run["untraced_seconds"])`` ``query`` spans by start),
as ``device_idle_pct`` does: the profiler slows the host.
"""
from __future__ import annotations

#: the class of each span name; a ``compile`` is a launch that had to
#: compile first (none in a warm window), and every other span (the
#: ``query`` itself, ``op:*``, ``quantum``, spills) is the interpreter
#: between launches
CLASSES = {"dispatch": "dispatch", "compile": "dispatch",
           "device-sync": "device-sync", "plan": "plan",
           "scan-stage": "scan-stage"}
EXECUTOR = "executor"


def innermost(spans: list) -> list:
    """``spans`` as (start, end, label), flattened to disjoint segments
    (start, end, label), each under the label of the innermost span
    that covers it: of those that cover an instant, the one that
    started last; of two that started together, the one that ends
    first. Instants no span covers are in no segment."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    cuts = sorted({t for a, b, _ in spans for t in (a, b)})
    segments, active, nxt = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        if active:
            segments.append((a, b, active[-1][2]))
    return segments


def self_seconds(query: dict, spans: list) -> dict:
    """{class: seconds} of one ``query`` span, from the spans of its
    trace that lie inside it (itself among them). The values add up to
    the span's duration."""
    lo, hi = query["start"], query["end"]
    out: dict = {}
    for a, b, name in innermost(
            [(max(s["start"], lo), min(s["end"], hi), s["name"])
             for s in spans if s["traceId"] == query["traceId"]
             and s["end"] > lo and s["start"] < hi]):
        cls = CLASSES.get(name, EXECUTOR)
        out[cls] = out.get(cls, 0.0) + (b - a)
    return out


def untraced_queries(run) -> list:
    """The ``query`` spans of the window's queries from before the
    profiler started."""
    queries = sorted((s for s in run["spans"] if s["name"] == "query"),
                     key=lambda s: s["start"])
    return queries[:len(run["untraced_seconds"])]


def mean_self_ms(run, cls: str):
    """Mean milliseconds a query of ``cls`` self time; None where the
    program has no ``dispatch`` span (it does not mark its launches, so
    its ``query`` and ``op:*`` spans would hold them unseen) or the
    window no untraced query."""
    spans = run["spans"]
    queries = untraced_queries(run)
    if not queries or not any(s["name"] == "dispatch" for s in spans):
        return None
    total = sum(self_seconds(q, spans).get(cls, 0.0) for q in queries)
    return 1e3 * total / len(queries)
