"""Lightweight span tracer with context propagation.

The role the reference spreads across QueryTracker/QueryStateMachine
timestamps and per-operator OperationTimer records, collapsed into one
span model: a span is a named [start, end) interval with a trace id, a
parent, and free-form attributes. Parentage flows through a contextvar,
so ``query -> plan -> operator -> device-sync/compile`` nests without
threading span handles through every call site; a span context can be
serialized into a task request (``Tracer.context``) and re-attached on a
worker (``Tracer.task_span``) so distributed traces stitch across the
wire by trace id.

Disabled (the default) the tracer must be invisible on hot paths:
``span()`` returns one shared no-op object and takes no lock; callers
wrapping per-batch work may additionally guard with ``TRACER.enabled``.
Enabled, a span entered with ``with`` also opens a
``jax.profiler.TraceAnnotation`` of the same name, so that whenever a
``jax.profiler`` session runs the engine's spans stand in the
``.xplane.pb`` host plane on the device trace's clock (nothing is
constructed while the tracer is off, and an annotation outside a
profiler session is a flag test).
Finished spans land in a bounded ring; ``export()`` snapshots them and
``chrome_trace()`` renders the Chrome ``chrome://tracing`` / Perfetto
JSON format (one "X" complete event per span, processes keyed by node,
threads keyed by task/query).
"""
from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Dict, Iterator, List, Optional

import jax

from .metrics import REGISTRY

#: the active span for the current thread/context (parent of new spans)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "presto_tpu_span", default=None)

#: perf_counter -> epoch anchor: spans are timed with the monotonic
#: clock but exported on the wall clock so spans from different
#: processes line up on one Chrome-trace timeline
_EPOCH_WALL = time.time()
_EPOCH_PERF = time.perf_counter()


def _now() -> float:
    return _EPOCH_WALL + (time.perf_counter() - _EPOCH_PERF)


def _annotation(name: str, attrs: Dict):
    """The profiler-side twin of a span: a ``TraceAnnotation`` named
    like it, its attributes as arguments. Only ever built while the
    tracer is on."""
    # a TraceMe encodes its arguments as "#k=v,k=v#"
    return jax.profiler.TraceAnnotation(
        name, **{k: str(v).replace(",", ";").replace("#", "")
                 for k, v in attrs.items()})


class Span:
    """One finished-or-running interval. Mutable while open; after
    ``end`` is set it is only read."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start",
                 "end", "node", "attrs", "_tracer", "_token", "_mark")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: Optional[str], attrs: Dict):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = f"{tracer.node}.{next(tracer._seq)}"
        self.node = tracer.node
        self.attrs = attrs
        self.start = _now()
        self.end: Optional[float] = None
        self._token = None
        self._mark = None

    # -- context-manager protocol --------------------------------------------
    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        self._mark = _annotation(self.name, self.attrs)
        self._mark.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        if exc is not None:
            self.attrs.setdefault("error", f"{type(exc).__name__}: {exc}")
        self.finish()
        return False

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def finish(self, end: Optional[float] = None) -> None:
        if self.end is None:
            self.end = _now() if end is None else end
            self._tracer._record(self)

    def to_dict(self) -> Dict:
        return {
            "name": self.name, "traceId": self.trace_id,
            "spanId": self.span_id, "parentId": self.parent_id,
            "node": self.node, "start": self.start,
            "end": self.end if self.end is not None else self.start,
            "attrs": dict(self.attrs),
        }


class _NoopSpan:
    """Shared do-nothing span: the disabled tracer's only allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def annotate(self, **attrs) -> None:
        pass

    def finish(self) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Process-wide span collector (one per process, ``TRACER``)."""

    def __init__(self, node: Optional[str] = None,
                 max_spans: int = 100_000):
        #: plain attribute (not a property) so hot paths pay one load
        self.enabled = os.environ.get("PRESTO_TPU_TRACE", "") \
            .strip().lower() not in ("", "0", "false", "off", "no")
        # random suffix: span ids must be globally unique across
        # processes for import_spans' dedup — containerized workers can
        # share a pid (every container's worker is pid 1)
        self.node = node or \
            f"pid-{os.getpid()}-{uuid.uuid4().hex[:6]}"
        self._seq = itertools.count(1)
        self._ring: deque = deque(maxlen=max_spans)
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------------
    def enable(self, flag: bool = True) -> None:
        global _LAST_LAUNCH
        self.enabled = flag
        if not flag:
            _LAST_LAUNCH = None

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def _record(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span.to_dict())

    # -- span creation -------------------------------------------------------
    def span(self, name: str, **attrs):
        """New child span of the current context (or a new trace root).
        Returns the shared no-op when disabled."""
        if not self.enabled:
            return NOOP_SPAN
        parent = _CURRENT.get()
        if parent is not None:
            return Span(self, name, parent.trace_id, parent.span_id, attrs)
        return Span(self, name, uuid.uuid4().hex[:16], None, attrs)

    def record_span(self, name: str, start: float, end: float,
                    **attrs) -> None:
        """A finished span of the current context from an interval
        measured elsewhere (``time.perf_counter`` readings): JAX reports
        a compile when it ends, with its duration."""
        if not self.enabled:
            return
        span = self.span(name, **attrs)
        span.start = _EPOCH_WALL + (start - _EPOCH_PERF)
        span.end = _EPOCH_WALL + (end - _EPOCH_PERF)
        self._record(span)

    def task_span(self, ctx: Optional[Dict], name: str, **attrs):
        """Span re-parented from a wire-carried context (a worker task
        resuming a coordinator trace). ``ctx`` is whatever ``context()``
        produced on the sending side; None/invalid degrades to a plain
        ``span()``."""
        if not self.enabled:
            return NOOP_SPAN
        if not isinstance(ctx, dict) or "traceId" not in ctx:
            return self.span(name, **attrs)
        return Span(self, name, str(ctx["traceId"]),
                    ctx.get("spanId"), attrs)

    def context(self) -> Optional[Dict]:
        """Wire-serializable context of the current span (ships inside
        task-create requests); None when disabled or outside a span."""
        if not self.enabled:
            return None
        cur = _CURRENT.get()
        if cur is None:
            return None
        return {"traceId": cur.trace_id, "spanId": cur.span_id}

    def wrap_iter(self, name: str, it: Iterator, **attrs) -> Iterator:
        """Span covering an iterator's lifetime (first ``next`` to
        exhaustion) — operator spans over streaming plan nodes. The
        parent is captured at call time, matching the plan structure
        rather than whichever operator happens to be draining. Each
        PULL is the current span while it runs (what it launches and
        waits for nests under the operator) and one profiler
        annotation: a ``TraceMe`` does not survive a ``yield``."""
        if not self.enabled:
            return it
        parent = _CURRENT.get()
        trace_id = parent.trace_id if parent is not None \
            else uuid.uuid4().hex[:16]
        parent_id = parent.span_id if parent is not None else None

        def gen():
            span = Span(self, name, trace_id, parent_id, attrs)
            batches = 0
            done = object()
            try:
                while True:
                    token = _CURRENT.set(span)
                    try:
                        with _annotation(name, attrs):
                            item = next(it, done)
                    finally:
                        _CURRENT.reset(token)
                    if item is done:
                        return
                    batches += 1
                    yield item
            finally:
                span.attrs["batches"] = batches
                span.finish()
        return gen()

    # -- export / merge ------------------------------------------------------
    def export(self, trace_id: Optional[str] = None) -> List[Dict]:
        with self._lock:
            spans = list(self._ring)
        if trace_id is not None:
            spans = [s for s in spans if s["traceId"] == trace_id]
        return spans

    def import_spans(self, spans: List[Dict]) -> int:
        """Merge foreign (worker-exported) spans, deduplicating by span
        id — in-process workers share this ring with the coordinator, so
        a harvest must not double-record. Returns spans added."""
        if not spans:
            return 0
        with self._lock:
            seen = {s.get("spanId") for s in self._ring}
            added = 0
            for s in spans:
                if not isinstance(s, dict) or s.get("spanId") in seen:
                    continue
                seen.add(s.get("spanId"))
                self._ring.append(s)
                added += 1
            return added


#: the process-wide tracer
TRACER = Tracer()

#: one leaf of the output of the engine's most recent launch, kept only
#: while the tracer is on: what ``device_drained`` asks. Process-wide:
#: one client and one executing thread; a prefetch thread's launch is a
#: launch like any other.
_LAST_LAUNCH = None


def note_launch(out):
    """Remember the launch that returned ``out`` as the engine's most
    recent one (the two launch sites call this inside their ``dispatch``
    span, so only while the tracer is on). Returns ``out``.

    Kept is a second handle on the last leaf's first shard, not the
    leaf: ``is_ready()`` of an array that a later launch was DONATED
    ends the process, of such a handle it raises."""
    global _LAST_LAUNCH
    leaves = jax.tree_util.tree_leaves(out)
    # an entry called while another program is traced launches nothing
    if leaves and not isinstance(leaves[-1], jax.core.Tracer):
        _LAST_LAUNCH = leaves[-1].addressable_data(0)
    return out


def device_drained() -> Optional[bool]:
    """Has the device finished everything the engine launched? It runs
    its queue in order, so the last launch's output being ready says
    the queue is empty: one flag read on the host, no device work.
    None where that is unknown: the tracer is off, nothing was launched
    since it went on, or the output was donated or deleted. Readers
    count unknown as "not drained"."""
    leaf = _LAST_LAUNCH
    if leaf is None or not TRACER.enabled:
        return None
    try:
        return bool(leaf.is_ready())
    except RuntimeError:
        return None


_SYNC_COUNTERS: Dict[str, tuple] = {}


def _sync_counters(what: str) -> tuple:
    pair = _SYNC_COUNTERS.get(what)
    if pair is None:
        pair = _SYNC_COUNTERS[what] = (
            REGISTRY.counter(f"device_sync_total.{what}"),
            REGISTRY.counter(f"device_sync_seconds_total.{what}"))
    return pair


def device_sync(what: str, value, fetch: bool = True, **attrs):
    """The one place the host reads a device value: ``value`` (arrays,
    any pytree; its producer already launched) fetched with
    ``jax.device_get`` and returned. ``fetch=False`` only waits for it
    (the mesh's ``input-drain``) and returns None. Always counted by
    kind: ``device_sync_total.<what>`` and the host seconds blocked in
    ``device_sync_seconds_total.<what>`` (one ``perf_counter`` pair).

    While the tracer is on the read is a ``device-sync`` span that
    first waits for the value and then fetches it, so the span splits:
    ``wait_s``, the seconds from its start until the value was ready
    (the device was working), and the rest, the fetch. ``drained`` says
    whether at that instant the engine's last launch had finished too
    (``device_drained``): True, the device stands idle from here until
    the next launch lands; False, work was queued behind the value and
    the readback cost the device nothing. The wait blocks nothing that
    the fetch would not have blocked."""
    count, seconds = _sync_counters(what)
    t0 = time.perf_counter()
    try:
        span = TRACER.span("device-sync", what=what, **attrs)
        if span is NOOP_SPAN:
            if fetch:
                return jax.device_get(value)
            jax.block_until_ready(value)
            return None
        with span:
            jax.block_until_ready(value)
            ready = _now()
            span.annotate(wait_s=ready - span.start,
                          drained=device_drained())
            if fetch:
                return jax.device_get(value)
            span.finish(ready)
            return None
    finally:
        count.inc()
        seconds.inc(time.perf_counter() - t0)


def current_span_ids() -> Dict:
    """Correlation ids of the active span for structured logging
    (``obs.log``): ``query_id``/``task_id``/``stage_id`` attributes
    plus the trace id, when a span is open on this context."""
    cur = _CURRENT.get()
    if not isinstance(cur, Span):
        return {}
    out = {k: cur.attrs[k] for k in ("query_id", "task_id", "stage_id")
           if k in cur.attrs}
    out["trace_id"] = cur.trace_id
    return out


# -- Chrome-trace (chrome://tracing / Perfetto) export -----------------------

def chrome_trace(spans: List[Dict]) -> Dict:
    """Render exported spans as the Chrome Trace Event JSON object
    format: one complete ("X") event per span with microsecond
    timestamps, processes keyed by node, lanes (tids) keyed by
    task/query so concurrent work stacks readably, plus "M" metadata
    events naming both."""
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    events: List[Dict] = []

    def pid_of(node: str) -> int:
        if node not in pids:
            pids[node] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name",
                           "pid": pids[node], "tid": 0,
                           "args": {"name": f"presto_tpu {node}"}})
        return pids[node]

    def tid_of(pid: int, lane: str) -> int:
        key = (pid, lane)
        if key not in tids:
            tids[key] = len(tids) + 1
            events.append({"ph": "M", "name": "thread_name",
                           "pid": pid, "tid": tids[key],
                           "args": {"name": lane}})
        return tids[key]

    for s in spans:
        attrs = s.get("attrs", {}) or {}
        pid = pid_of(s.get("node", "?"))
        lane = str(attrs.get("task_id") or attrs.get("query_id")
                   or s.get("traceId", "main"))
        start = float(s.get("start", 0.0))
        end = float(s.get("end", start))
        events.append({
            "ph": "X", "name": s.get("name", "?"), "cat": "presto_tpu",
            "ts": round(start * 1e6, 1),
            "dur": round(max(end - start, 0.0) * 1e6, 1),
            "pid": pid, "tid": tid_of(pid, lane),
            "args": {"traceId": s.get("traceId"),
                     "spanId": s.get("spanId"),
                     "parentId": s.get("parentId"), **attrs},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, spans: List[Dict]) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(spans), f)
    return path
