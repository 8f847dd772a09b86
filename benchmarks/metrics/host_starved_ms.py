"""Operators: milliseconds a query of the device's idle time that the
program itself can vouch for, a LOWER BOUND: the union of the stretches
that begin where the device was seen drained (a drained sync's ready
instant; the start of a starved launch) and end where the next
``dispatch`` span ends, mean over the window's untraced queries. What
``device_idle_pct`` holds beyond it lies in stretches that begin with
the device busy. The ``[feed]`` line gives its three parts. None where
the program marks neither its syncs nor its launches. See
``feedtime.py``."""
import feedtime


def read(run):
    parts = feedtime.feed(run)["starved_ms"]
    return None if parts is None else sum(parts.values())
