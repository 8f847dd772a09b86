"""Seconds per query as the analyst feels them: the whole window (first
POST to the return of the query in flight at ``--seconds``) over the
queries completed in it. Host clock at the client."""


def read(run):
    return run["window_s"] / len(run["seconds"])
