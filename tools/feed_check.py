#!/usr/bin/env python3
"""The program's account of a starved device beside the device's own
clock, over the SAME traced queries of one benchmark cell:

    chiprun -- python3 tools/feed_check.py --workload tpch_sf10_q6 --seed 7

A traced run of the cell through the benchmark's own harness (a TPU, as
``benchmarks/run.py``), with two things kept that the harness throws
away: the profiler's trace, read through ``benchmarks/gapnames.py`` (the
device's idle seconds inside the ``bench:query`` marks, by the span of
the program that covers each gap), and the tracer's spans of the TRACED
queries, read through ``benchmarks/feedtime.py`` (``host_starved`` and
its three parts, the syncs by kind). It prints both, the share of the
idle time that the program's lower bound explains, and the client's
seconds of the window's untraced and traced queries (the tracer's cost:
compare the first with ``query_s`` of a ``--trace 0`` run), then the
result line. ``--root`` runs another checkout's program and harness (the
parent's, unpacked into a directory of this one) under this reader.
"""
import time
T_START = time.perf_counter()

import argparse  # noqa: E402
import glob      # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import shutil    # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    # the checkout under test first; this one's readers where it has none
    sys.path[:0] = [os.path.join(root, "benchmarks"), root]
    sys.path.append(os.path.join(ROOT, "benchmarks"))
    os.chdir(root)

    import presto_tpu  # noqa: F401  (turns 64-bit types on, first)
    if not on_tpu():
        print("feed_check: JAX found no TPU; nothing is measured on a CPU",
              file=sys.stderr)
        return 2
    import feedtime
    import gapnames
    import harness
    from presto_tpu.obs.trace import TRACER

    kept: dict = {}

    class KeptSlice(harness.TraceSlice):
        def reduce(self) -> dict:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            kept["trace"], kept["dir"] = files[0], self.dir
            return harness.tracereduce.reduce_trace(files[0])

    loop = harness.closed_loop

    def closed_loop(*a, **kw):
        out = loop(*a, **kw)
        kept["done"] = out[0]
        return out

    harness.TraceSlice, harness.closed_loop = KeptSlice, closed_loop
    result = harness.run_cell(harness.load_cell(args.workload), args.seed,
                              args.seconds, True, T_START)

    done = kept["done"]
    plain = [d[3] for d in done if not d[4]]
    traced = [d[3] for d in done if d[4]]
    print(f"[check] client seconds a query: {len(plain)} untraced mean "
          f"{sum(plain) / len(plain)!r}, {len(traced)} traced mean "
          f"{sum(traced) / len(traced)!r}")

    spans = TRACER.export()     # the ring is cleared at the next run only
    queries = sorted((s for s in spans if s["name"] == "query"),
                     key=lambda s: s["start"])
    feeds = {"untraced": feedtime.feed_of(spans, queries[:len(plain)]),
             "TRACED": feedtime.feed_of(spans, queries[len(plain):])}
    for label, f in feeds.items():
        print(f"[check] {label}: " + (feedtime.feed_line(f) or "the program "
              "splits no sync and marks no launch"))
    bound = feeds["TRACED"]["starved_ms"]

    busy, marks, annotations, plane = gapnames.read_trace(kept["trace"])
    shutil.rmtree(kept["dir"], ignore_errors=True)
    s = gapnames.summary(gapnames.name_gaps(busy, marks, annotations))
    n = len(marks)
    in_marks = sum(b - a for a, b in marks) / 1e9
    print(f"[check] device ops from {plane}; {n} traced queries over "
          f"{in_marks:.6f}s inside their marks, idle {s['idle_s']:.6f}s in "
          f"{s['gaps']} gaps: {1e3 * s['idle_s'] / n:.3f} ms a query")
    for name, secs in s["by_span"]:
        print(f"[check]   {1e3 * secs / n:10.3f} ms a query  "
              f"{100 * secs / s['idle_s']:6.2f}%  {name}")
    print("[check] by span and detail (the twenty largest):")
    for name, secs in s["by_label"][:20]:
        print(f"[check]   {1e3 * secs / n:10.3f} ms a query  "
              f"{100 * secs / s['idle_s']:6.2f}%  {name}")
    if bound is not None:
        print(f"[check] host_starved_ms over the same {n} queries "
              f"{sum(bound.values()):.3f}: "
              f"{100 * sum(bound.values()) * n / (1e3 * s['idle_s']):.2f}% "
              f"of the device's idle time inside the marks")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
