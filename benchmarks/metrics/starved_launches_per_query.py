"""Operators: launches a query that found the device dry: the program's
``dispatch`` spans whose ``starved`` is true (nothing was queued when
the launch began: the host is behind the device), mean over the
window's untraced queries; unknown counts as not starved. Beside
``launches_per_query``, which counts them all. None where the program
does not mark its launches so. See ``feedtime.py``."""
import feedtime


def read(run):
    return feedtime.feed(run)["starved_launches"]
