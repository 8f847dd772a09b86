"""Operators: live groups of the finished grouped state a query
(``agg_state_groups_total``, counted in ``exec/spill.py``
``AggSpillBuffer`` when the state is finished, from the count the last
cut read back: a state that never outgrew 4096 lanes is not read back
and not counted), summed over a query's group-bys. What the state holds
on the device at its largest. None where the program lacks the
counter."""


def read(run):
    groups = run["counters"].get("agg_state_groups_total")
    if groups is None:
        return None
    return groups / len(run["seconds"])
