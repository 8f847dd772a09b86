"""Kernels: device milliseconds a traced query in the engine's
expression programs (``jit_expr_*``: filters and projections), among
the TEN busiest programs of the traced slice, which is what the
reduction keeps. See ``programnames.py``."""
import programnames


def read(run):
    return programnames.device_ms(run, "expr")
