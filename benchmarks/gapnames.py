#!/usr/bin/env python3
"""A device's idle gaps, each put down to the span of the program that
covers it:

    python3 benchmarks/gapnames.py <trace.xplane.pb>

While its tracer is on, every span of the program (``query``, ``plan``,
``op:<Node>``, ``dispatch``, ``device-sync``, ``scan-stage``) is also a
``TraceAnnotation``, so a profiler trace holds them on the host plane on
the device trace's clock. This takes the idle gaps of the device inside
the harness's ``bench:query`` marks (``tracereduce.union``, ``clip``,
``gaps``) and names each by the INNERMOST such annotation that covers
the gap's midpoint (``spantime.innermost``: of those that cover it, the
one that started last);
a ``dispatch`` is named with its program, a ``device-sync`` with what
it reads. It prints the idle seconds by name and the ten longest gaps.

The harness removes its trace once reduced; keep one with a copy of the
harness whose ``TraceSlice.reduce`` leaves the files. Wiring this into
``tracereduce.reduce_trace``, so that the ledger's ``idle_gaps`` stop
reading "query in flight", is one line for a ``benchmark`` PR.

On a trace recorded on the CPU (the rehearsal's fixture: no device
plane) the executor threads' ``ThunkExecutor::Execute`` events stand
for the device's operations; no number of such a trace is a device's.
"""
from __future__ import annotations

import bisect
import sys

import spantime
import tracereduce

#: the program's annotations on the host plane, by name or prefix
SPANS = ("query", "plan", "dispatch", "device-sync", "scan-stage")
OP_PREFIX = "op:"
#: the argument that tells one span of a name from another
DETAIL = {"dispatch": "program", "device-sync": "what"}
#: a gap inside a mark that no span of the program covers: the client
#: and the server around the ``query`` span
OUTSIDE = "outside the program's spans (protocol)"

CPU_THREADS = "tf_XLAPjRtCpuClient/"
CPU_EXECUTE = "ThunkExecutor::Execute"


def label(name: str, stats: dict) -> str:
    detail = stats.get(DETAIL.get(name, ""))
    return f"{name}[{detail}]" if detail else name


def read_trace(path: str) -> tuple:
    """(busy intervals of the busiest device, the marks, the program's
    annotations as (start, end, label), the plane read for the device
    with every device's busy seconds)."""
    from jax.profiler import ProfileData
    device: dict = {}
    cpu: list = []
    marks, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracereduce.DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            src = lines.get(tracereduce.OPS_LINE) \
                or lines.get(tracereduce.MODULES_LINE)
            if src is not None:
                device[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns)
                    for e in src.events]
        elif plane.name == tracereduce.HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == tracereduce.QUERY_MARK:
                        marks.append(iv)
                    elif e.name in SPANS or e.name.startswith(OP_PREFIX):
                        stats = {k: v for k, v in e.stats}
                        spans.append(iv + (label(e.name, stats),))
                    elif (ln.name.startswith(CPU_THREADS)
                          and e.name == CPU_EXECUTE):
                        cpu.append(iv)
    if device:
        # the busiest chip: in a four-chip trace the first chip's line
        # can be all but empty (PERF.md section 7)
        busy = {p: tracereduce.union(ivs) for p, ivs in device.items()}
        secs = {p: sum(b - a for a, b in ivs) / 1e9
                for p, ivs in busy.items()}
        plane = max(sorted(secs), key=secs.get)
        shown = ", ".join(f"{p} {secs[p]:.6f}s" for p in sorted(secs))
        return busy[plane], marks, spans, \
            f"{plane} (busy in the whole trace: {shown})"
    return tracereduce.union(cpu), marks, spans, \
        f"{tracereduce.HOST_PLANE} {CPU_THREADS}* (a CPU trace)"


def name_gaps(busy: list, marks: list, spans: list) -> list:
    """[(seconds, label)] of every idle gap inside a mark, longest
    first."""
    segments = spantime.innermost(spans)
    starts = [seg[0] for seg in segments]
    out = []
    for lo, hi in tracereduce.union(marks):
        for a, b in tracereduce.gaps(tracereduce.clip(busy, lo, hi), lo, hi):
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            inside = i >= 0 and mid <= segments[i][1]
            out.append(((b - a) / 1e9,
                        segments[i][2] if inside else OUTSIDE))
    out.sort(reverse=True)
    return out


def summary(named: list) -> dict:
    """Idle seconds by label and by span name (the label less its
    detail), the share put down to a span of the program, the ten
    longest gaps."""
    by_label: dict = {}
    by_span: dict = {}
    for secs, lab in named:
        by_label[lab] = by_label.get(lab, 0.0) + secs
        span = lab.split("[")[0]
        by_span[span] = by_span.get(span, 0.0) + secs
    total = sum(secs for secs, _ in named)
    inside = total - by_label.get(OUTSIDE, 0.0)
    return {
        "idle_s": total, "gaps": len(named),
        "named_share": inside / total if total else None,
        "by_span": sorted(by_span.items(), key=lambda kv: -kv[1]),
        "by_label": sorted(by_label.items(), key=lambda kv: -kv[1]),
        "longest": named[:10],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[0] + "\n\n    python3 "
              "benchmarks/gapnames.py <trace.xplane.pb>", file=sys.stderr)
        return 2
    busy, marks, spans, plane = read_trace(argv[0])
    if not busy or not marks:
        print(f"gapnames: {len(busy)} busy intervals and {len(marks)} "
              f"{tracereduce.QUERY_MARK} marks in {argv[0]}: nothing to "
              f"name", file=sys.stderr)
        return 1
    s = summary(name_gaps(busy, marks, spans))
    in_marks = sum(b - a for a, b in tracereduce.union(marks)) / 1e9
    print(f"device ops from {plane}; {len(marks)} queries over "
          f"{in_marks:.6f}s, idle {s['idle_s']:.6f}s in {s['gaps']} gaps, "
          f"{100 * s['named_share']:.2f}% of it inside a span of the "
          f"program")
    print("idle seconds by span:")
    for name, secs in s["by_span"]:
        print(f"  {secs:10.6f}  {100 * secs / s['idle_s']:6.2f}%  {name}")
    print("idle seconds by span and detail (the twenty largest):")
    for name, secs in s["by_label"][:20]:
        print(f"  {secs:10.6f}  {100 * secs / s['idle_s']:6.2f}%  {name}")
    print("the ten longest gaps:")
    for secs, name in s["longest"]:
        print(f"  {1e3 * secs:10.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
