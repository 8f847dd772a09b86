"""Operators: programs the engine launches per query of the window:
jit-cache entries (``jit_cache_invocations_total``, what
``dispatches_per_query`` counts) plus expression programs
(``expr_program_invocations_total``: filters and projections, launched
outside the jit cache), window delta over queries. Eager ops are
launched by JAX, not by the engine, and are not in it. None where the
program does not count its expression programs."""


def read(run):
    c = run["counters"]
    if "expr_program_invocations_total" not in c:
        return None
    return (c.get("jit_cache_invocations_total", 0.0)
            + c["expr_program_invocations_total"]) / len(run["seconds"])
