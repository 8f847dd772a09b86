"""Operators: milliseconds a query that the host spends blocked on the
device: self time of the program's ``device-sync`` spans (every place
the host reads a device value: liveness counts, build summaries, error
flags, the answer's fetch), mean over the window's untraced queries.
See ``spantime.py``."""
import spantime


def read(run):
    return spantime.mean_self_ms(run, "device-sync")
