"""Operators: merges of the grouped state a query
(``agg_state_merges_total``, counted in ``exec/spill.py``
``AggSpillBuffer`` at every merge of buffered partials into the running
state and at the final): each is one ``grouped_aggregate`` program in
merge mode over the state and what was buffered. None where the program
lacks the counter."""


def read(run):
    merges = run["counters"].get("agg_state_merges_total")
    if merges is None:
        return None
    return merges / len(run["seconds"])
