"""Device profiling and cost attribution.

The role of the reference's OperatorStats device accounting ("Presto on
GPUs" motivates operator-level accelerator time; tf.data's
input-bound-vs-compute-bound framing is the verdict we surface): host
wall times lie on an async-dispatch backend, so this module holds the
engine's *device-level* truth:

- ``EXECUTABLES`` — one record per compiled jit entry (``ops/jitcache``
  and the fused-chain pipelines): compile seconds, invocation count,
  cumulative *device* time, and lazy XLA introspection
  (``lowered.cost_analysis()`` FLOPs / bytes-accessed,
  ``compiled.memory_analysis()`` arg/output/temp bytes). Surfaced as
  the ``system.runtime.executables`` table and the EXPLAIN ANALYZE
  "Executables" section.
- a **profile context** (``profiled()``): while active, every cached
  jit dispatch is bracketed with ``jax.block_until_ready`` so the
  measured interval is device time, and attributed to the plan operator
  whose iterator frame made the call (``operator_scope``, set by
  ``exec/stats.StatsCollector.wrap``). Off (the default) the only cost
  per dispatch is one contextvar load and an int increment.
- **the compile listener** (``_on_compile``): ONE listener on JAX's
  ``backend_compile_duration`` event counts every XLA compile of the
  process (``xla_compile_total``/``xla_compile_seconds_total``: first
  calls, retraces of later shape buckets, expression programs, eager
  ops, persistent-cache loads) and charges the ones whose ``fun_name``
  is the program being dispatched on that thread to its record
  (``compiles``/``compile_seconds``, ``jit_compile_*``).
- **HBM telemetry** (``sample_hbm``): ``device.memory_stats()`` gauges,
  sampled on worker heartbeats and by the local
  ``system.runtime.nodes`` fallback.

Caveat worth stating once: bracketing with ``block_until_ready``
serializes the dispatch pipeline — profile mode trades overlap for
truth. That is why it is a per-query session property (``profile``),
auto-enabled under EXPLAIN ANALYZE (which already pays per-batch syncs
for row counts), and never on for plain queries.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax.monitoring

from .metrics import REGISTRY
from .trace import TRACER

#: active profile session (None = off) — checked on every jit dispatch
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "presto_tpu_profile", default=None)

#: (stats_collector, plan_node) of the operator whose iterator frame is
#: currently executing — innermost wins, set by StatsCollector.wrap
_OP: contextvars.ContextVar = contextvars.ContextVar(
    "presto_tpu_operator", default=None)

_DEVICE_SECONDS = REGISTRY.counter("jit_cache_device_seconds_total")
#: every cached-entry dispatch (incremented by ops/jitcache on the hot
#: path — one lock-guarded add, the registry's standard cost)
INVOCATIONS = REGISTRY.counter("jit_cache_invocations_total")


class ExecutableRecord:
    """One cached jit entry's ledger. Cheap fields (compile seconds,
    invocations, device seconds) are filled on the hot path; XLA
    introspection is computed lazily from the first call's avals so a
    query never pays a second compile unless someone asks."""

    __slots__ = ("name", "static_key", "fun_name", "compiles",
                 "compile_seconds", "invocations", "device_time_s",
                 "created_at", "evicted", "_key_repr", "_fn", "_avals",
                 "_analysis", "_lock", "_alock")

    def __init__(self, name: str, static_key: str):
        self.name = name
        self.static_key = static_key
        #: what JAX's compile event calls this record's program
        #: (``jit(op_grouped_aggregate)``); set at registration
        self.fun_name: Optional[str] = None
        self.compiles = 0
        self.compile_seconds = 0.0
        self.invocations = 0
        self.device_time_s = 0.0
        self.created_at = time.time()
        # set when the registry's leak-guard cap drops this record; the
        # owning _TimedEntry keeps dispatching into it, so the next
        # dispatch readmits it (counts survive, nothing goes invisible)
        self.evicted = False
        self._key_repr = static_key
        self._fn = None
        self._avals = None
        self._analysis: Optional[Dict] = None
        # counter lock, held for nanoseconds on the dispatch path;
        # analysis gets its own lock because analyze() can hold it for
        # an entire XLA compile — a dispatch must never wait on that
        self._lock = threading.Lock()
        self._alock = threading.Lock()

    def note_invocation(self) -> None:
        # locked: the profile context deliberately follows pipelines
        # onto producer/driver threads, so one record takes concurrent
        # dispatches — an unlocked += would drop counts
        with self._lock:
            self.invocations += 1

    def note_device_time(self, seconds: float) -> None:
        with self._lock:
            self.device_time_s += seconds

    def note_compile(self, seconds: float) -> None:
        """One XLA compile of this record's program, as JAX's own event
        reported it (the compile listener below): first calls, retraces
        for later shape buckets and persistent-cache loads alike."""
        with self._lock:
            self.compiles += 1
            self.compile_seconds += seconds

    def capture_avals(self, fn, args) -> None:
        """Keep the first call's abstract shapes for lazy analysis. The
        analysis describes that first bucket — scan padding keeps
        buckets stable within a query, and the numbers are
        per-invocation estimates, not an audit."""
        if self._avals is None:
            try:
                import jax
                import jax.numpy as jnp
                self._avals = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        jnp.shape(x), jnp.result_type(x)), args)
                self._fn = fn
            except Exception:
                self._avals = None

    def analyze(self) -> Dict:
        """Lazy XLA introspection: FLOPs / bytes-accessed from
        ``lowered.cost_analysis()`` (per invocation), arg/output/temp
        bytes + generated code size from ``compiled.memory_analysis()``.
        The memory half pays one extra XLA compile the first time it is
        asked for (the jit dispatch cache is separate) — which is why
        this runs at table-read/EXPLAIN-render time, never per call.
        Fields are None when the backend doesn't support the API."""
        with self._alock:
            if self._analysis is not None:
                return self._analysis
            out: Dict = {"flops": None, "bytes_accessed": None,
                         "arg_bytes": None, "output_bytes": None,
                         "temp_bytes": None, "generated_code_bytes": None}
            fn, avals = self._fn, self._avals
            if fn is not None and avals is not None:
                lowered = None
                try:
                    lowered = fn.lower(*avals)
                    ca = lowered.cost_analysis() or {}
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else {}
                    if "flops" in ca:
                        out["flops"] = float(ca["flops"])
                    if "bytes accessed" in ca:
                        out["bytes_accessed"] = float(ca["bytes accessed"])
                except Exception:
                    pass
                try:
                    if lowered is not None:
                        ma = lowered.compile().memory_analysis()
                        if ma is not None:
                            out["arg_bytes"] = int(
                                ma.argument_size_in_bytes)
                            out["output_bytes"] = int(
                                ma.output_size_in_bytes)
                            out["temp_bytes"] = int(ma.temp_size_in_bytes)
                            out["generated_code_bytes"] = int(
                                ma.generated_code_size_in_bytes)
                except Exception:
                    pass
            self._analysis = out
            return out

    def to_row(self, analyze: bool = True) -> Dict:
        doc = {
            "name": self.name, "static_key": self.static_key,
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 6),
            "invocations": self.invocations,
            "device_time_s": round(self.device_time_s, 6),
        }
        a = self.analyze() if analyze else (self._analysis or {})
        for k in ("flops", "bytes_accessed", "arg_bytes", "output_bytes",
                  "temp_bytes", "generated_code_bytes"):
            doc[k] = a.get(k)
        return doc


class ExecutableRegistry:
    """Process-wide (name, static key) -> ExecutableRecord, bounded.
    The feed of ``system.runtime.executables``."""

    def __init__(self, max_records: int = 4096):
        self._records: Dict[Tuple[str, str], ExecutableRecord] = {}
        self._max = max_records
        self._lock = threading.Lock()

    def register(self, name: str, static_key=(),
                 program: Optional[str] = None) -> ExecutableRecord:
        """The record of ``(name, static_key)``; ``program`` is the name
        its function was jitted under (``op_lookup_join``), by which
        the compile listener knows the record's compiles."""
        # identity keys on the FULL repr — two fused chains sharing a
        # long prefix must stay distinct records; only the displayed
        # static_key column is truncated
        key_repr = (static_key if isinstance(static_key, str)
                    else repr(static_key))
        k = (name, key_repr)
        rec = self._records.get(k)
        if rec is None:
            with self._lock:
                rec = self._records.get(k)
                if rec is None:
                    if len(self._records) >= self._max:
                        self._evict_one_locked()
                    shown = (key_repr if len(key_repr) <= 160
                             else key_repr[:157] + "...")
                    rec = ExecutableRecord(name, shown)
                    rec._key_repr = key_repr
                    self._records[k] = rec
        if program is not None:
            rec.fun_name = f"jit({program})"
        return rec

    def _evict_one_locked(self) -> None:
        # drop the coldest record (fewest invocations, then oldest) —
        # the cap is a leak guard, not a working set (4096 entries is
        # far beyond any real query mix), so the victim should be a
        # one-off key shape, never a hot import-time entry
        victim = min(self._records,
                     key=lambda x: (self._records[x].invocations,
                                    self._records[x].created_at))
        self._records[victim].evicted = True
        del self._records[victim]

    def readmit(self, rec: ExecutableRecord) -> None:
        """Re-insert a record the cap evicted while its _TimedEntry was
        still live (the entry caches the record forever, so without
        this the busiest kernels could update a detached ledger the
        tables never see). Called from the dispatch path only when
        ``rec.evicted`` is set — i.e. ~never."""
        k = (rec.name, rec._key_repr)
        with self._lock:
            if k not in self._records:
                if len(self._records) >= self._max:
                    self._evict_one_locked()
                self._records[k] = rec
            rec.evicted = False

    def snapshot(self, analyze: bool = True) -> List[Dict]:
        with self._lock:
            recs = list(self._records.values())
        recs.sort(key=lambda r: (-r.device_time_s, -r.compile_seconds))
        return [r.to_row(analyze=analyze) for r in recs]

    def reset(self) -> None:
        with self._lock:
            # live _TimedEntries keep dispatching into the dropped
            # records; marking them evicted lets the next dispatch
            # readmit each, so a reset zeroes the view without making
            # cached kernels permanently invisible
            for rec in self._records.values():
                rec.evicted = True
            self._records.clear()


#: the process-wide executable registry
EXECUTABLES = ExecutableRegistry()


# -- profile context ----------------------------------------------------------

class ProfileSession:
    """Marker held by the ``_ACTIVE`` contextvar while a query profiles
    (one per profiled query; carries nothing yet — attribution state
    lives on the query's StatsCollector)."""

    __slots__ = ()


_SESSION = ProfileSession()


@contextlib.contextmanager
def profiled(on: bool = True):
    """Enable device-time bracketing for jit dispatches made under this
    context (same thread/context only — background prefetch threads stay
    unbracketed so overlapped staging is never serialized)."""
    if not on:
        yield
        return
    token = _ACTIVE.set(_SESSION)
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def profiling_active() -> bool:
    return _ACTIVE.get() is not None


@contextlib.contextmanager
def operator_scope(stats, node):
    """Attribute jit dispatches made under this context to ``node`` on
    ``stats`` (a StatsCollector). Innermost scope wins — nested operator
    iterators re-set it around their own frames."""
    token = _OP.set((stats, node))
    try:
        yield
    finally:
        _OP.reset(token)


def current_operator():
    return _OP.get()


def profiled_call(record: ExecutableRecord, fn, args):
    """One bracketed dispatch under a profile context: run, block until
    the device finishes, charge the interval (a host bracket around a
    blocked call, not a device clock) to the executable and to the
    operator whose frame made the call. Every call under the context
    is bracketed, so no queued async work can leak into the interval."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    record.note_device_time(dt)
    _DEVICE_SECONDS.inc(dt)
    op = _OP.get()
    if op is not None:
        stats, node = op
        rd = getattr(stats, "record_device", None)
        if rd is not None:
            rd(node, dt, record)
    return out


# -- verdict (tf.data's input-bound vs compute-bound framing) -----------------

def cost_verdict(stats) -> Optional[Dict]:
    """Classify a profiled query: device time attributed to non-scan
    operators (compute) vs scan-side host time — scan operators'
    exclusive wall (decode/staging) plus consumer prefetch stall
    (input). None when nothing was profiled."""
    from ..planner.plan import TableScanNode
    compute_s = 0.0
    scan_wall_s = 0.0
    for node, st in list(stats.by_node.items()):
        dev = getattr(st, "device_time_s", 0.0)
        if isinstance(node, TableScanNode):
            child_wall = sum(
                (stats.stats_for(c).wall_s
                 if stats.stats_for(c) is not None else 0.0)
                for c in node.children)
            scan_wall_s += max(st.wall_s - child_wall, 0.0)
        else:
            compute_s += dev
    input_s = scan_wall_s + getattr(stats, "prefetch_stall_s", 0.0)
    if compute_s <= 0.0 and input_s <= 0.0:
        return None
    if input_s > 2.0 * compute_s:
        verdict = "input-bound"
    elif compute_s > 2.0 * input_s:
        verdict = "compute-bound"
    else:
        verdict = "balanced"
    return {"verdict": verdict, "compute_s": compute_s,
            "input_s": input_s}


# -- HBM telemetry ------------------------------------------------------------

def sample_hbm(devices=None, registry=None) -> List[Dict]:
    """Sample ``device.memory_stats()`` into per-device gauges
    (``hbm_in_use_bytes.<dev>`` / ``hbm_peak_bytes.<dev>``) and return
    the per-device docs. Backends without memory stats (XLA:CPU returns
    None) yield an empty list — callers treat that as "no HBM story",
    not an error."""
    reg = registry if registry is not None else REGISTRY
    if devices is None:
        try:
            import jax
            devices = jax.local_devices()
        except Exception:
            return []
    out: List[Dict] = []
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        in_use = int(ms.get("bytes_in_use", 0) or 0)
        peak = int(ms.get("peak_bytes_in_use", in_use) or in_use)
        limit = int(ms.get("bytes_limit", 0) or 0)
        label = f"{getattr(d, 'platform', 'dev')}{getattr(d, 'id', 0)}"
        reg.gauge(f"hbm_in_use_bytes.{label}").set(in_use)
        reg.gauge(f"hbm_peak_bytes.{label}").set(peak)
        out.append({"device": label, "device_id": getattr(d, "id", 0),
                    "bytes_in_use": in_use,
                    "peak_bytes_in_use": peak, "bytes_limit": limit})
    return out


def hbm_totals(devices=None, registry=None) -> Dict[str, int]:
    """Summed HBM sample for heartbeat payloads: zeros when the backend
    has no memory stats (the coordinator then shows 0, not stale)."""
    docs = sample_hbm(devices, registry)
    return {
        "bytesInUse": sum(d["bytes_in_use"] for d in docs),
        "peakBytes": sum(d["peak_bytes_in_use"] for d in docs),
        "devices": len(docs),
    }


# -- the compile listener -----------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_XLA_COMPILES = REGISTRY.counter("xla_compile_total")
_XLA_COMPILE_S = REGISTRY.counter("xla_compile_seconds_total")
_JIT_COMPILES = REGISTRY.counter("jit_compile_total")
_JIT_COMPILE_S = REGISTRY.counter("jit_compile_seconds_total")
#: fixed-bucket histogram alongside the counter: compile-time p95
#: becomes visible on /v1/metrics (jit_compile_seconds_bucket/_sum/
#: _count) while the long-standing _total sum keeps old scrapes working
_JIT_COMPILE_HIST = REGISTRY.histogram("jit_compile_seconds")

#: ``.record``: the ExecutableRecord whose program this thread launched
#: last. JAX compiles inside the launching call, on its thread, so a
#: compile event whose ``fun_name`` is that record's is that record's
#: compile; any other name is an eager op (a ``jnp`` call outside every
#: jit, or one made while a program traces) and is charged to no record.
DISPATCHING = threading.local()


def note_launch(rec: ExecutableRecord) -> None:
    """The ledger's part of every launch of a record's program (a
    jit-cache entry's or an expression program's)."""
    if rec.evicted:
        EXECUTABLES.readmit(rec)
    rec.note_invocation()
    DISPATCHING.record = rec


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    _XLA_COMPILES.inc()
    _XLA_COMPILE_S.inc(duration)
    fun_name = kw.get("fun_name")
    rec = getattr(DISPATCHING, "record", None)
    if rec is not None and rec.fun_name == fun_name:
        rec.note_compile(duration)
        _JIT_COMPILES.inc()
        _JIT_COMPILE_S.inc(duration)
        _JIT_COMPILE_HIST.observe(duration)
    if TRACER.enabled:
        end = time.perf_counter()
        TRACER.record_span("compile", end - duration, end,
                           program=fun_name, seconds=round(duration, 6))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
