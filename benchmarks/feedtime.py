"""When the program left the device with nothing to do, for the five
feed readers (``metrics/sync_wait_ms.py``, ``sync_fetch_ms.py``,
``drains_per_query.py``, ``starved_launches_per_query.py``,
``host_starved_ms.py``).

While its tracer is on the program splits every readback and marks
every launch (``presto_tpu/obs/trace.py``): a ``device-sync`` span
carries ``wait_s``, the seconds from its start until the value it reads
was ready (the device was working: the span's self time splits there
into the wait and the fetch, so the two add up to what
``device_sync_ms`` reads), and ``drained``, whether at that
instant the last program launched had finished too (nothing is queued:
the device stands idle until the next launch lands); a ``dispatch``
span carries ``starved``, whether the device had nothing queued when
the launch began. Unknown (None) counts as "not drained".

From these the program's own LOWER BOUND of the device's idle time in
a query, ``host_starved``: the union of the stretches that begin where
the device was seen drained (a drained sync's ready instant, ``start +
wait_s``; the start of a starved launch) and end where the next
``dispatch`` span ends (work is queued again; the query's end where
there is none). It has three parts, an instant counted once under the
first that covers it: the FETCH of a drained sync (the answer crossing
to the host), the REFILL from that sync's end to the next launch's
end (the interpreter and the launch), and starved LAUNCHES outside
such a stretch. Eager ``jnp`` ops launch outside the engine's two
sites and are not seen (``eager_device_ms`` is their size).

As ``spantime.py``: means over the window's queries from BEFORE the
profiler started. ``feed(run)`` is computed once a run, kept on it, and
printed as the run's one ``[feed]`` line; ``feed_of`` reads any other
queries' spans (the traced ones, beside a kept device trace).
"""
from __future__ import annotations

import bisect

import spantime

PARTS = ("fetch", "refill", "launch")


def query_feed(query: dict, spans: list) -> tuple:
    """One ``query`` span's ({what: [count, wait s, fetch s, drains]},
    {part: s} of host_starved, starved launches); None for a table
    where no ``device-sync`` span carries ``wait_s``, for the other two
    where no ``dispatch`` span carries ``starved``."""
    lo, hi = query["start"], query["end"]
    mine = sorted((s for s in spans if s["traceId"] == query["traceId"]
                   and s["end"] > lo and s["start"] < hi),
                  key=lambda s: s["start"])
    launches = [s for s in mine if s["name"] == "dispatch"]
    starts = [d["start"] for d in launches]
    by_what, pieces, ready = {}, [], {}
    for i, s in enumerate(mine):
        attrs = s["attrs"]
        if s["name"] != "device-sync" or "wait_s" not in attrs:
            continue
        ready[i] = s["start"] + attrs["wait_s"]
        row = by_what.setdefault(attrs.get("what", "?"), [0, 0.0, 0.0, 0])
        row[0] += 1
        if attrs.get("drained") is True:
            row[3] += 1
            nxt = bisect.bisect_left(starts, ready[i])
            refilled = launches[nxt]["end"] if nxt < len(launches) else hi
            pieces.append((ready[i], min(s["end"], refilled), "fetch"))
            pieces.append((s["end"], refilled, "refill"))
    # a sync's SELF time (what the innermost-span rule leaves it: a
    # launch another thread makes meanwhile is that launch's), split
    # where its value was ready: the wait before, the fetch after
    for a, b, i in spantime.innermost(
            [(max(s["start"], lo), min(s["end"], hi), i)
             for i, s in enumerate(mine)]):
        if i in ready:
            row = by_what[mine[i]["attrs"].get("what", "?")]
            row[1] += max(min(b, ready[i]) - a, 0.0)
            row[2] += max(b - max(a, ready[i]), 0.0)
    marked = [d for d in launches if "starved" in d["attrs"]]
    starved = [d for d in marked if d["attrs"]["starved"] is True]
    pieces += [(d["start"], d["end"], "launch") for d in starved]
    parts, covered = dict.fromkeys(PARTS, 0.0), lo
    for a, b, part in sorted(pieces):
        a, b = max(a, covered), min(b, hi)
        if b > a:
            parts[part] += b - a
            covered = b
    return (by_what or None, parts if by_what and marked else None,
            len(starved) if marked else None)


def _mean(values: list):
    return sum(values) / len(values) if values else None


def feed_of(spans: list, queries: list) -> dict:
    """Means a query over ``queries``: ``by_what`` {what: [count, wait
    ms, fetch ms, drains]}, ``starved_ms`` {part: ms},
    ``starved_launches``; each None where the program's spans lack what
    it is read from."""
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["traceId"], []).append(s)
    rows = [query_feed(q, by_trace[q["traceId"]]) for q in queries]
    tables = [r[0] for r in rows if r[0] is not None]
    by_what: dict = {}
    for table in tables:
        for what, row in table.items():
            acc = by_what.setdefault(what, [0.0] * 4)
            for i, scale in enumerate((1, 1e3, 1e3, 1)):
                acc[i] += scale * row[i] / len(tables)
    bounds = [r[1] for r in rows if r[1] is not None]
    return {
        "queries": len(rows),
        "by_what": by_what or None,
        "starved_ms": {p: 1e3 * _mean([b[p] for b in bounds])
                       for p in PARTS} if bounds else None,
        "starved_launches": _mean([r[2] for r in rows
                                   if r[2] is not None]),
    }


def feed(run) -> dict:
    """``feed_of`` the window's untraced queries: computed by the first
    of the five readers, kept on ``run`` for the others, and printed
    then as the run's one ``[feed]`` line."""
    if "feed" not in run:
        run["feed"] = feed_of(run["spans"], spantime.untraced_queries(run))
        line = feed_line(run["feed"])
        if line:
            print(line, flush=True)
    return run["feed"]


def total(table, column: int):
    """A column of ``by_what`` summed over the kinds (1 wait ms, 2 fetch
    ms, 3 drains); None without a table."""
    if table is None:
        return None
    return sum(row[column] for row in table.values())


def feed_line(f: dict) -> str:
    """The by-kind table and the bound's parts on one line; empty where
    the program marks neither."""
    if f["by_what"] is None and f["starved_launches"] is None:
        return ""
    kinds = "; ".join(
        f"{what} x{row[0]:.2f} wait {row[1]:.3f} fetch {row[2]:.3f} "
        f"drains {row[3]:.2f}"
        for what, row in sorted((f["by_what"] or {}).items(),
                                key=lambda kv: -(kv[1][1] + kv[1][2])))
    line = (f"[feed] a query of {f['queries']} (by what: count, wait ms, "
            f"fetch ms, drains): {kinds or 'no split sync'}")
    if f["starved_ms"] is not None:
        p = f["starved_ms"]
        line += (f" | host_starved_ms {sum(p.values()):.3f} = fetch while "
                 f"drained {p['fetch']:.3f} + refill {p['refill']:.3f} + "
                 f"starved launches {p['launch']:.3f}")
    if f["starved_launches"] is not None:
        line += f" | starved launches {f['starved_launches']:.2f}"
    return line
