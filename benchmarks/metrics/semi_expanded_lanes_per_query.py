"""Operators: lanes a query that ``expand_join`` produced for residual
semi joins (``semi_join_expanded_lanes_total``, counted in
``exec/local.py`` ``_SemiJoinNode`` for every probe batch of the m:n
`expand` form: the batch's capacity times the bucket of the build's
largest multiplicity). 0 where every residual is decided on a summary
by key (the `keyed` form). None where the program lacks the counter."""


def read(run):
    lanes = run["counters"].get("semi_join_expanded_lanes_total")
    if lanes is None:
        return None
    return lanes / len(run["seconds"])
