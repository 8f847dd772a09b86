#!/usr/bin/env python
"""Which line of the engine issues each eager op.

A program in a device trace under a JAX primitive's own name
(``jit_scatter-add``, ``jit__take``, ``jit_slice``) is a ``jnp`` call
made outside every jit of the engine (``ops/jitcache.named_jit`` names
the engine's own programs ``jit_op_*``, ``jit_expr_*``, ``jit_smap_*``).
JAX compiles such an op where it is first called, on the calling thread,
so the stack at its compile event is its call site. This runs one
statement in a fresh process on whatever backend JAX has (the CPU does:
the call sites are the same) and prints, for each eager op, the engine's
innermost frames that issued it.

    JAX_PLATFORMS=cpu python tools/eager_sites.py --sf 0.05 \\
        --rows-per-batch 65536 "select ... from lineitem ..."
"""
from __future__ import annotations

import argparse
import collections
import os
import sys
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sql")
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--rows-per-batch", type=int, default=1 << 16)
    ap.add_argument("--frames", type=int, default=3)
    args = ap.parse_args(argv)

    import jax.monitoring

    import presto_tpu
    from presto_tpu.exec.runner import LocalRunner
    from presto_tpu.obs.profiler import COMPILE_EVENT

    pkg = os.path.dirname(os.path.abspath(presto_tpu.__file__))
    sites: dict = collections.defaultdict(collections.Counter)

    def on_compile(event, duration, **kw):
        name = kw.get("fun_name", "?")
        if event != COMPILE_EVENT or name.startswith(
                ("jit(op_", "jit(expr_", "jit(smap_")):
            return
        frames = [f for f in traceback.extract_stack()
                  if f.filename.startswith(pkg)][-args.frames:]
        sites[name][" <- ".join(
            f"{os.path.relpath(f.filename, pkg)}:{f.lineno}:{f.name}"
            for f in reversed(frames)) or "?"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    LocalRunner(tpch_sf=args.sf,
                rows_per_batch=args.rows_per_batch).execute(args.sql)
    for name, by_site in sorted(sites.items(),
                                key=lambda kv: -sum(kv[1].values())):
        print(f"{name} {sum(by_site.values())}")
        for site, n in by_site.most_common(4):
            print(f"    {n:4d} {site}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
