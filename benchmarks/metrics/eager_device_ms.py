"""Kernels: device milliseconds a traced query in eager ops (programs
under none of the engine's three prefixes: a ``jnp`` call outside every
jit, ``jit_scatter-add``, ``jit__take``), among the TEN busiest
programs of the traced slice, which is what the reduction keeps. See
``programnames.py``."""
import programnames


def read(run):
    return programnames.device_ms(run, "eager")
