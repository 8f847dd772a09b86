"""Kernels: device milliseconds a traced query in the engine's operator
programs (``jit_op_*`` jit-cache entries, ``jit_smap_*`` mesh
programs), among the TEN busiest programs of the traced slice, which
is what the reduction keeps. See ``programnames.py``."""
import programnames


def read(run):
    return programnames.device_ms(run, "op")
