"""Operators: wall milliseconds a query spent making the filtering
sides of its residual semi joins: the time inside the program's
``semi-build`` spans (``exec/local.py`` ``_SemiJoinNode``: from the
filtering side's first batch to the prepared layout the probe reads),
INCLUSIVE of the launches and the readbacks made there, which are the
build's cost (the summary's group-by, its merges, the build's cut, its
table); spans that overlap count once. Mean over the window's untraced
queries. None where the program has no such span."""
import spantime

SPAN = "semi-build"


def read(run):
    spans = [s for s in run["spans"] if s["name"] == SPAN]
    queries = spantime.untraced_queries(run)
    if not queries or not spans:
        return None
    total = 0.0
    for q in queries:
        lo, hi = q["start"], q["end"]
        total += sum(b - a for a, b, _ in spantime.innermost(
            [(max(s["start"], lo), min(s["end"], hi), SPAN)
             for s in spans if s["traceId"] == q["traceId"]
             and s["end"] > lo and s["start"] < hi]))
    return 1e3 * total / len(queries)
