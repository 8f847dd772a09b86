"""Compile: seconds of XLA backend compiles (or compile-cache loads)
during set-up, from JAX's own monitoring event, summed over threads."""


def read(run):
    return sum(e[1] for e in run["compiles_setup"])
