"""Operators: milliseconds a query of the grouped state's merges on the
host: self time of the program's ``agg-merge`` spans (``exec/spill.py``
``AggSpillBuffer``: every merge and the final), mean over the window's
untraced queries. Self time as ``spantime`` defines it: the launches
(``dispatch``) and the readbacks (``device-sync``) a merge makes are
theirs, so this is what the merge costs the interpreter beside them
(concatenation, slicing, bookkeeping). None where the program has no
such span or marks no launch (``spantime.mean_self_ms``)."""
import spantime

SPAN = "agg-merge"


def read(run):
    spans = run["spans"]
    queries = spantime.untraced_queries(run)
    if (not queries or not any(s["name"] == SPAN for s in spans)
            or not any(s["name"] == "dispatch" for s in spans)):
        return None
    total = 0.0
    for q in queries:
        lo, hi = q["start"], q["end"]
        total += sum(b - a for a, b, name in spantime.innermost(
            [(max(s["start"], lo), min(s["end"], hi), s["name"])
             for s in spans if s["traceId"] == q["traceId"]
             and s["end"] > lo and s["start"] < hi]) if name == SPAN)
    return 1e3 * total / len(queries)
