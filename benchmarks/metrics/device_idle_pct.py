"""Device: share of a query in which no operation ran on the chip, for
a query at its own speed: 1 - (device busy seconds per traced query,
the union of the ``XLA Ops`` intervals) / (the client's median seconds
per query of the same window BEFORE the profiler was started; the
median, because one query in some forty stalls on the host for seconds).

The profiler slows the host, not the device (``tpch_sf10_q1``: a traced
query takes 0.94 s against 0.71 s with the same 0.23 s busy; my chip
runs, PR 25), so the idle share of the traced slice itself, which the
driver works out from ``device.busy_s`` and ``device.window_s``, reads
higher than this one. A run too short to hold an untraced query reports
nothing."""
import statistics


def read(run):
    t, untraced = run["trace"], run["untraced_seconds"]
    if not t or not t["queries"] or not untraced:
        return None
    busy = t["busy_s"] / t["queries"]
    return 100.0 * (1.0 - busy / statistics.median(untraced))
