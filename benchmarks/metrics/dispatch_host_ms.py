"""Operators: milliseconds a query that the host spends launching
programs: self time of the program's ``dispatch`` spans (one per launch
of a jit-cache entry, an expression program or a mesh program: argument
handling, the jit cache's lookup, the enqueue; a launch that compiles
counts whole), mean over the window's untraced queries. See
``spantime.py``."""
import spantime


def read(run):
    return spantime.mean_self_ms(run, "dispatch")
