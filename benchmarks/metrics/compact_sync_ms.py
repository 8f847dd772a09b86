"""Operators: milliseconds a query that the host spends blocked on the
compactor's liveness readback: self time of the program's
``device-sync`` spans whose ``what`` is ``compaction-liveness``
(``exec/local.py`` ``_compactor.maybe_compact``: the wait for the
filter or the probe that made the batch), mean over the window's
untraced queries. The share of ``device_sync_ms`` that a compaction
decided on the device would take off the host. None where the program
counts no compaction (``compact_checked_total``) or marks no launch
(``spantime.mean_self_ms``)."""
import spantime

WHAT = "compaction-liveness"


def read(run):
    if "compact_checked_total" not in run["counters"]:
        return None
    # every other sync under another name: an executor span to spantime
    spans = [s if s["name"] != "device-sync"
             or s["attrs"].get("what") == WHAT
             else {**s, "name": "device-sync:other"} for s in run["spans"]]
    return spantime.mean_self_ms({**run, "spans": spans}, "device-sync")
