"""Operators: milliseconds a query that a readback takes once its value
is ready: the self time of the program's ``device-sync`` spans AFTER
that instant (``wait_s`` after the span's start), mean over the
window's untraced queries: the answer crossing to the host, during which a drained device
stands idle. None where the program does not split its syncs. See
``feedtime.py``."""
import feedtime


def read(run):
    return feedtime.total(feedtime.feed(run)["by_what"], 2)
