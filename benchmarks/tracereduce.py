"""From a profiler trace (``.xplane.pb``) to device busy time, idle
share, the programs that took most device time and the longest idle
gaps. Reads the file with ``jax.profiler.ProfileData`` and nothing else.

What a TPU v5e trace holds (looked at by hand, PR 25): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one per HLO operation that ran) and ``Async XLA Ops`` (copies in
flight, which overlap the operations and are not counted as busy); a
plane ``/host:CPU`` whose ``python`` lines carry the harness's own
``TraceAnnotation`` events. All start times are nanoseconds on one
clock.
"""
from __future__ import annotations

#: the harness wraps every traced query in a TraceAnnotation of this name
QUERY_MARK = "bench:query"

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo, hi) -> list:
    """The stretches of ``lo..hi`` that the disjoint, sorted ``busy``
    leaves uncovered."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append([at, a])
        at = max(at, b)
    if hi > at:
        out.append([at, hi])
    return out


def reduce_events(device_ops: dict, device_modules: dict, marks: list,
                  top: int = 10) -> dict:
    """``device_ops``: {device: [(start_ns, end_ns)]} of the operations
    that ran; ``device_modules``: {device: [(name, start_ns, end_ns)]};
    ``marks``: [(start_ns, end_ns)] of the traced queries. The traced
    slice runs from the first mark's start to the last mark's end (the
    whole trace where there is no mark)."""
    if not device_ops or not any(device_ops.values()):
        return {}
    if marks:
        lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    else:
        every = [iv for ivs in device_ops.values() for iv in ivs]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    busy = {d: clip(union(ivs), lo, hi) for d, ivs in device_ops.items()}
    busy_ns = [sum(b - a for a, b in ivs) for ivs in busy.values()]
    by_name: dict = {}
    for mods in device_modules.values():
        for name, a, b in mods:
            if b > lo and a < hi:
                by_name[name] = by_name.get(name, 0.0) \
                    + (min(b, hi) - max(a, lo))
    n_dev = len(device_ops)
    inflight = union(marks)
    idle = []
    for a, b in gaps(busy[min(busy)], lo, hi):
        mid = (a + b) / 2
        inside = any(x <= mid <= y for x, y in inflight)
        idle.append(["query in flight" if inside else "between queries",
                     (b - a) / 1e9])
    idle.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "queries": len(marks),
        "device_ops": [[n, s / n_dev / 1e9] for n, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle[:top],
    }


def reduce_trace(path: str) -> dict:
    """The reduction of one ``.xplane.pb``; {} where no operation ran on
    a device."""
    from jax.profiler import ProfileData
    ops, modules, marks = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            src = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if src is None:
                continue
            ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns)
                               for e in src.events]
            mod = lines.get(MODULES_LINE)
            modules[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in (mod.events if mod is not None else ())]
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                marks.extend((e.start_ns, e.start_ns + e.duration_ns)
                             for e in ln.events if e.name == QUERY_MARK)
    return reduce_events(ops, modules, marks)
