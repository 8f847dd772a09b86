"""Operators: milliseconds a query that the host spends blocked in a
readback WHILE THE DEVICE WORKS: the self time of the program's
``device-sync`` spans BEFORE the value they read was ready (``wait_s``
after the span's start), mean over the window's untraced queries. Nothing to gain
here on the host. With ``sync_fetch_ms`` it adds up to the self time
``device_sync_ms`` reports. None where the program does not split its
syncs. See ``feedtime.py``."""
import feedtime


def read(run):
    return feedtime.total(feedtime.feed(run)["by_what"], 1)
