"""Kernels: device milliseconds a traced query in the group-by's
programs, the ``breakdown.device_ops`` rows named
``jit_op_grouped_aggregate*`` (``ops/aggregation.py`` through
``ops/jitcache.py``: partials, merges and finals alike), among the TEN
busiest programs of the traced slice, which is what the reduction
keeps. None without a trace or where no such row is among the ten."""

PREFIX = "jit_op_grouped_aggregate"


def read(run):
    t = run["trace"]
    if not t or not t.get("queries") or not t.get("device_ops"):
        return None
    rows = [secs for name, secs in t["device_ops"] if name.startswith(PREFIX)]
    if not rows:
        return None
    return 1e3 * sum(rows) / t["queries"]
