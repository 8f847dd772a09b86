"""Device seconds by the kind of program, for the three device readers
(``metrics/op_device_ms.py``, ``expr_device_ms.py``,
``eager_device_ms.py``).

The program names every XLA module it builds (``ops/jitcache.py``,
``named_jit``): ``jit_op_<entry>`` a jit-cache entry (join, group-by,
fused chain, compaction), ``jit_smap_<stage>_<site>`` a mesh program,
``jit_expr_<kind>_<digest>`` an expression program (filter, project).
A module under any other name (``jit_scatter-add``, ``jit__take``,
``jit_slice``) is by that fact an eager op: a ``jnp`` call outside
every jit of the engine.

The readers see what ``run["trace"]["device_ops"]`` holds, and the
reduction (``tracereduce.py``) keeps the TEN busiest programs of the
traced slice only: the three kinds add up to those ten, not to the
device's busy seconds.
"""
from __future__ import annotations

KINDS = {"op": ("jit_op_", "jit_smap_"), "expr": ("jit_expr_",)}


def kind_of(name: str) -> str:
    """``op``, ``expr`` or ``eager`` for one row of ``device_ops``
    (``jit_<fn>(<fingerprint>)``)."""
    for kind, prefixes in KINDS.items():
        if name.startswith(prefixes):
            return kind
    return "eager"


def device_ms(run, kind: str):
    """Milliseconds a traced query on the device in programs of
    ``kind``, among the ten busiest; None without a trace, and for a
    program that does not name its modules (every row would read as
    eager): the one that does also counts its expression programs, in
    ``expr_program_invocations_total``."""
    t = run["trace"]
    if (not t or not t.get("queries") or not t.get("device_ops")
            or "expr_program_invocations_total" not in run["counters"]):
        return None
    return 1e3 * sum(secs for name, secs in t["device_ops"]
                     if kind_of(name) == kind) / t["queries"]
