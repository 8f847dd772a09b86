"""Operators: readbacks a query that left the device with nothing to
do: the program's ``device-sync`` spans whose ``drained`` is true (when
the value was ready the last program launched had finished too), mean
over the window's untraced queries; unknown counts as not drained. At
most the window's ``device_sync_total`` family over its queries. None
where the program does not split its syncs. See ``feedtime.py``."""
import feedtime


def read(run):
    return feedtime.total(feedtime.feed(run)["by_what"], 3)
