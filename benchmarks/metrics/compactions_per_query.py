"""Operators: batches per query that the per-operator compactor shrank
behind a filter or a join (``compact_applied_total``, counted in
``exec/local.py`` ``_compactor.maybe_compact`` from the liveness count
it reads back anyway): each one is a host sync and an eager
``Batch.compact``. None where the program lacks the counter."""


def read(run):
    applied = run["counters"].get("compact_applied_total")
    if applied is None:
        return None
    return applied / len(run["seconds"])
