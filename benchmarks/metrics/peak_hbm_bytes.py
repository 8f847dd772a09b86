"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip
after the window. Over the scan-cache limit a query stages again."""


def read(run):
    return run["peak_bytes"] or None
