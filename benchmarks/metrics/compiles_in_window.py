"""Compile: programs compiled (or loaded from the compile cache) inside
the measured window. Expected 0: the warm-up has sent every statement."""


def read(run):
    return len(run["compiles_window"])
