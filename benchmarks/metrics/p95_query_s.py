"""95th percentile of the client's seconds per query over ALL queries of
the window (nearest rank). Host clock at the client."""
import math


def read(run):
    secs = sorted(run["seconds"])
    return secs[max(0, math.ceil(0.95 * len(secs)) - 1)]
