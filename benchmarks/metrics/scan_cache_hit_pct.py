"""Scan staging: share of the window's scans that the device scan cache
served (``scan_cache_hit_total`` over hits plus misses). A miss stages
the table again from the generator."""


def read(run):
    c = run["counters"]
    hits = c.get("scan_cache_hit_total", 0.0)
    total = hits + c.get("scan_cache_miss_total", 0.0)
    if not total:
        return None
    return 100.0 * hits / total
