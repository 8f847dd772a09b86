"""Operators: dispatches of jit-cache entries per query of the window
(``jit_cache_invocations_total``, which ``obs/profiler.py`` counts on
every call of an executable in its registry)."""


def read(run):
    return run["counters"].get("jit_cache_invocations_total", 0.0) \
        / len(run["seconds"])
