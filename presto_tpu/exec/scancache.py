"""Device-resident scan cache + asynchronous prefetching scan pipeline.

The input side of the engine, shared by the local executor
(exec/local.py) and the cluster worker task path (server/worker.py):

- **ScanCache** — a memory-accounted, LRU, cross-query cache of decoded
  device column sets keyed by (connector instance, catalog, table,
  split, column set, table data-version, and the pushdown where the
  connector applies it: ``spi.Connector.applies_pushdown``). tf.data
  (PAPERS.md) and "Accelerating Presto with GPUs" both found that the
  accelerator starves unless decoded input is cached and pipelined; here a warm
  re-run of a scan-heavy query replays device-resident batches instead
  of re-generating/decoding/transferring every split. Entries are
  accounted against a dedicated ``memory.QueryMemoryPool`` (so the
  resident set is bounded and observable) and invalidated on connector
  writes through ``connectors.spi.notify_data_change`` — the same write
  path that already invalidates the sqlite connector's TableStats
  cache. Connectors that cannot attest a data version
  (``Connector.data_version`` returns None, e.g. the live
  system.runtime tables) are never cached.

- **Prefetching pipeline** — bounded per-split reorder queues filled by
  background threads: split N+1 decodes and stages to the device
  (``jax.device_put``) while the consumer's kernels chew on split N.
  Delivery stays in deterministic split order (physical row order feeds
  order-sensitive downstream semantics). Consumer-side waits are
  recorded as prefetch stalls — the histogram that says whether a query
  is input-bound — and credited back to the fair device scheduler
  (exec/taskexec.py) so stalled queries aren't billed device time they
  never used.

- **Bucketed capacity padding** — the ragged final chunk of a split
  pads up to the scan stream's standard power-of-two bucket, so the
  jit caches (ops/jitcache.py) reuse one executable per operator
  instead of recompiling per residual size.

Observability: ``scan_cache_{hit,miss,insert}_total``,
``scan_cache_evicted_bytes_total``, ``scan_cache_resident_bytes``,
``scan_prefetch_stall_seconds``, ``scan_prefetch_batches_total`` — all
flowing through the shared registry into ``system.runtime.metrics``,
``/v1/metrics``, and the EXPLAIN ANALYZE scan-cache line
(planner/printer.format_scan_cache_summary).

Session knobs (docs/perf.md): ``scan_cache`` (default true; the escape
hatch), ``scan_prefetch``, ``scan_prefetch_depth``,
``scan_pad_batches``, ``scan_threads``. The resident LIMIT is
process-wide on purpose — ``scan-cache.max-bytes`` in
config.properties or ``CACHE.set_limit`` — never a session property
(one session must not evict every other session's cache).
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
import weakref
from collections import OrderedDict
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax

from .._devtools.lockcheck import checked_lock, checked_rlock, guarded_by
from ..batch import Batch, bucket_capacity
from ..connectors import spi
from ..memory import QueryMemoryPool, batch_device_bytes
from ..obs import flight as _flight
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER
from .failpoints import FAILPOINTS

_HITS = REGISTRY.counter("scan_cache_hit_total")
_MISSES = REGISTRY.counter("scan_cache_miss_total")
_INSERTS = REGISTRY.counter("scan_cache_insert_total")
_INVALIDATED = REGISTRY.counter("scan_cache_invalidated_total")
_EVICTED_BYTES = REGISTRY.counter("scan_cache_evicted_bytes_total")
_RESIDENT = REGISTRY.gauge("scan_cache_resident_bytes")
_STALL = REGISTRY.histogram("scan_prefetch_stall_seconds")
_PREFETCH_BATCHES = REGISTRY.counter("scan_prefetch_batches_total")
_SHARED_ATTACH = REGISTRY.counter("scan_shared_attach_total")

#: longest a query waits on another query's in-flight decode before
#: giving up and decoding solo (robustness: a wedged producer must not
#: wedge its attached consumers)
SHARED_WAIT_S = 30.0

#: default resident-set bound for the process-wide cache; overridable
#: via config.properties ``scan-cache.max-bytes`` or CACHE.set_limit
DEFAULT_CACHE_BYTES = 2 << 30


def _freeze(v):
    """Recursively hashable form of split/pushdown payloads (connector
    split info is opaque and may carry lists)."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


class _Entry:
    __slots__ = ("batches", "nbytes", "ctx", "conn_ref")

    def __init__(self, batches, nbytes, ctx, conn_ref):
        self.batches = batches
        self.nbytes = nbytes
        self.ctx = ctx
        self.conn_ref = conn_ref


class _InFlight:
    """One split decode in progress: attached queries wait on ``event``
    and read ``batches`` (None = the producer failed or abandoned —
    waiters retry, possibly becoming the producer themselves)."""

    __slots__ = ("event", "batches")

    def __init__(self):
        self.event = threading.Event()
        self.batches: Optional[List[Batch]] = None


class ScanCache:
    """Cross-query LRU of decoded device split data, accounted against
    its own memory pool (the reference has no analogue — Presto re-reads
    the source per query; the closest cousins are Alluxio-style local
    caches and tf.data's ``cache()``, which this is, device-resident).

    Serving plane: the cache additionally brokers **shared-scan
    batching** — N concurrent queries missing on the same (table,
    split, columns, pushdown, version) key attach to ONE in-flight
    decode (``join_inflight``/``finish_inflight``) instead of racing N
    duplicate decodes, the "shared work across concurrent consumers of
    the same table" idea from 'Efficient Tabular Data Preprocessing of
    ML Pipelines' (PAPERS.md)."""

    #: guarded-field contracts (lockcheck): entry map and in-flight
    #: decode table only under the cache lock
    _entries = guarded_by(attr="_lock")
    _inflight = guarded_by(attr="_lock")

    def __init__(self, limit_bytes: int = DEFAULT_CACHE_BYTES):
        self.pool = QueryMemoryPool(limit_bytes)
        self._entries: "OrderedDict[Tuple, _Entry]" = OrderedDict()
        self._inflight: dict = {}
        self._lock = checked_rlock("scancache.entries")

    # -- keying ---------------------------------------------------------------
    @staticmethod
    def key(conn, catalog: str, split, columns, pushdown, version,
            rows_per_batch: int = 0):
        """Raises TypeError when split info / pushdown are unhashable —
        callers treat that split as uncacheable. ``rows_per_batch`` is
        part of the key: a consumer with a different batch-size setting
        must miss (and re-decode at its own granularity), not replay
        another runner's 32x-larger batches into operators sized for
        small ones."""
        k = (id(conn), catalog, split.table.schema, split.table.table,
             _freeze(split.info), tuple(columns), _freeze(pushdown),
             _freeze(version), int(rows_per_batch))
        hash(k)
        return k

    # -- lookup / insert ------------------------------------------------------
    def get(self, key, conn) -> Optional[List[Batch]]:
        return self.get_any([key], conn)

    def get_any(self, keys, conn,
                count_miss: bool = True) -> Optional[List[Batch]]:
        """First hit among ``keys`` (one hit/miss accounted for the
        whole probe — callers pass [effective-pushdown key,
        static-pushdown key]: an entry produced WITHOUT dynamic bounds
        is a superset the engine re-filters anyway, so it serves a
        bounds-carrying consumer correctly). ``count_miss=False`` for
        speculative probes that will be retried with accounting."""
        with self._lock:
            for key in keys:
                e = self._entries.get(key)
                if e is None:
                    continue
                if e.conn_ref() is not conn:
                    # id() reuse after a connector was collected: never
                    # serve another connector's data for a recycled
                    # address
                    self._drop(key, e)
                    continue
                self._entries.move_to_end(key)
                _HITS.inc()
                return e.batches
            for key in keys:
                # the columns of a wider scan of the same split (TPC-H
                # Q21 reads lineitem three times, twice four columns
                # and once two of them): ONE resident copy serves both
                wide = self._wider(key)
                if wide is not None and wide[1].conn_ref() is conn:
                    self._entries.move_to_end(wide[0])
                    _HITS.inc()
                    names = list(key[self._COLUMNS])
                    return [b.select(names) for b in wide[1].batches]
            if count_miss:
                _MISSES.inc()
            return None

    #: where a key holds its columns
    _COLUMNS = 5

    def _wider(self, key):
        """(key, entry) of an entry that differs from ``key`` only in
        its columns and holds all of ``key``'s, or None; under the
        lock."""
        want = set(key[self._COLUMNS])
        for k, e in self._entries.items():
            if self._same_but_columns(k, key) \
                    and want <= set(k[self._COLUMNS]):
                return k, e
        return None

    @classmethod
    def _same_but_columns(cls, a, b) -> bool:
        at = cls._COLUMNS
        return a[:at] == b[:at] and a[at + 1:] == b[at + 1:]

    def put(self, key, conn, batches: List[Batch]) -> bool:
        nbytes = sum(batch_device_bytes(b) for b in batches)
        with self._lock:
            if key in self._entries or self._wider(key) is not None:
                return True          # first writer won; identical data
            # version re-check under the lock: a write that landed while
            # this scan was decoding already bumped data_version (and
            # its invalidate found nothing to drop) — inserting under
            # the stale version key would leave an unreachable entry
            # squatting on reserved bytes until LRU pressure clears it
            ver_fn = getattr(conn, "data_version", None)
            if ver_fn is not None and _freeze(ver_fn(key[3])) != key[7]:
                return False
            if nbytes > self.pool.limit:
                return False         # can never fit: don't flush the LRU
            self._sweep_dead()
            ctx = self.pool.context("scan-cache-entry")
            while not self.pool.try_reserve(nbytes, ctx):
                if not self._entries:
                    ctx.close()
                    return False
                self._evict_lru()
            # what this entry's columns make redundant goes
            at = self._COLUMNS
            for k in [k for k in self._entries
                      if self._same_but_columns(k, key)
                      and set(k[at]) < set(key[at])]:
                self._drop(k, self._entries[k])
            self._entries[key] = _Entry(batches, nbytes, ctx,
                                        weakref.ref(conn))
            _INSERTS.inc()
            _RESIDENT.set(self.pool.reserved)
            return True

    # -- shared-scan batching -------------------------------------------------
    def join_inflight(self, key) -> Tuple[_InFlight, bool]:
        """(record, is_owner): the first caller per key becomes the
        owner (it decodes and MUST call :meth:`finish_inflight` on every
        exit path); later callers attach and wait on ``record.event``."""
        with self._lock:
            fl = self._inflight.get(key)
            if fl is not None:
                return fl, False
            fl = self._inflight[key] = _InFlight()
            return fl, True

    def finish_inflight(self, key, batches: Optional[List[Batch]]) -> None:
        """Publish the owner's outcome: the complete staged batch list,
        or None when the decode failed/was abandoned (waiters retry)."""
        with self._lock:
            fl = self._inflight.pop(key, None)
        if fl is not None:
            fl.batches = batches
            fl.event.set()

    # -- eviction / invalidation ---------------------------------------------
    def _drop(self, key, e: _Entry) -> None:
        del self._entries[key]
        e.ctx.close()
        _RESIDENT.set(self.pool.reserved)

    def _evict_lru(self) -> None:
        key, e = next(iter(self._entries.items()))
        _EVICTED_BYTES.inc(e.nbytes)
        self._drop(key, e)

    def _sweep_dead(self) -> None:
        """Drop entries whose connector was garbage-collected (their
        weakref is dead): long-lived processes churn through short-lived
        runners, and dead entries are pure resident-set waste."""
        for key in [k for k, e in self._entries.items()
                    if e.conn_ref() is None]:
            self._drop(key, self._entries[key])

    def invalidate(self, conn=None, table: Optional[str] = None) -> None:
        """Drop entries for a connector (and optionally one table). Part
        of the connector write path via spi.notify_data_change — the
        same path that invalidates per-connector stats caches."""
        with self._lock:
            victims = []
            for key, e in self._entries.items():
                ref = e.conn_ref()
                if ref is None:
                    victims.append(key)   # dead connector: always drop
                    continue
                if conn is not None and ref is not conn:
                    continue
                if table is not None and key[3] != table:
                    continue
                victims.append(key)
            for key in victims:
                self._drop(key, self._entries[key])
            if victims:
                _INVALIDATED.inc(len(victims))

    def clear(self) -> None:
        with self._lock:
            for key in list(self._entries):
                self._drop(key, self._entries[key])

    def set_limit(self, limit_bytes: int) -> None:
        with self._lock:
            self.pool.limit = int(limit_bytes)
            while self._entries and self.pool.reserved > self.pool.limit:
                self._evict_lru()

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return int(self.pool.reserved)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: the process-wide cache (one device per process, like taskexec.GLOBAL)
CACHE = ScanCache()

# connector writes invalidate through the shared SPI notification hook
spi.on_data_change(lambda conn, table: CACHE.invalidate(conn, table))


# -- scan options -------------------------------------------------------------

@dataclasses.dataclass
class ScanOptions:
    cache: bool = True
    prefetch: bool = True
    pad: bool = True
    threads: int = 2
    depth: int = 4
    #: attach concurrent identical-split misses to one in-flight decode
    shared: bool = True


def options_from_session(session) -> ScanOptions:
    # the resident LIMIT is deliberately NOT a session property: the
    # cache is process-wide, and one session's knob must not resize
    # (and evict from) every other session's cache — size it via
    # config.properties scan-cache.max-bytes or CACHE.set_limit
    from ..planner.planner import bool_property
    props = session.properties
    return ScanOptions(
        cache=bool_property(session, "scan_cache", True),
        prefetch=bool_property(session, "scan_prefetch", True),
        pad=bool_property(session, "scan_pad_batches", True),
        threads=int(props.get("scan_threads", 2)),
        depth=int(props.get("scan_prefetch_depth", 4)),
        shared=bool_property(session, "shared_scan", True))


class _PadTracker:
    """Max-capacity-so-far tracker for one scan stream: a batch smaller
    than the stream's established bucket (the ragged final chunk) pads
    up to it, bounded by the rows_per_batch bucket, so one executable
    per operator serves the whole stream."""

    __slots__ = ("_lock", "_max", "ceiling")

    def __init__(self, ceiling: int):
        self._lock = checked_lock("scancache.pad")
        self._max = 0
        self.ceiling = ceiling

    def target(self, capacity: int) -> int:
        with self._lock:
            if capacity > self._max:
                self._max = capacity
                return capacity
            return min(self._max, self.ceiling)


# -- the scan pipeline --------------------------------------------------------

def scan_splits(conn, catalog: str, columns: Sequence[str],
                splits: Sequence, pushdown_fn: Callable[[], object],
                rows_per_batch: int, opts: ScanOptions,
                record_split=None, check_cancel=None,
                stats=None, static_pushdown=None) -> Iterator[Batch]:
    """Stream a table scan's batches: per-split cache lookup, background
    decode+stage prefetch, deterministic split-order delivery, bucketed
    capacity padding. ``pushdown_fn`` is re-evaluated when each split
    starts (dynamic join bounds may arrive while earlier splits stream —
    the bounds in force become part of that split's cache key).
    ``static_pushdown`` (the plan-time bounds, sans dynamic-filter
    additions) keys a FALLBACK lookup: a cached entry produced without
    the dynamic bounds is a superset the join machinery re-filters, so
    it may serve a bounds-carrying re-run — warm hits stay deterministic
    even when dynamic bounds race the scan."""
    if not splits:
        return
    columns = tuple(columns)
    version = None
    cacheable = opts.cache
    if cacheable:
        # getattr: duck-typed connector doubles predate the SPI method
        ver_fn = getattr(conn, "data_version", None)
        version = ver_fn(splits[0].table.table) if ver_fn else None
        cacheable = version is not None
    # getattr: duck-typed connector doubles predate the SPI attribute
    applies_pushdown = getattr(conn, "applies_pushdown", True)
    pad = _PadTracker(bucket_capacity(max(int(rows_per_batch), 1))) \
        if opts.pad else None
    # inline (no prefetch threads): split_batches runs inside the
    # consumer's device-scheduler quantum — attach-waiting there would
    # hold the device while the owner may need quanta to finish its own
    # inline decode (whole-device stall). Inline scans therefore never
    # ATTACH; they still register ownership and publish, so threaded
    # peers (which wait on background threads, outside any quantum) can
    # ride their decode.
    inline_scan = not opts.prefetch or opts.threads <= 1
    # the prefetch workers run on threads of their own, where no span
    # is current: their scan-stage spans join this query's trace
    trace_ctx = TRACER.context()

    def split_keys(split, pushdown):
        """[effective key, static-pushdown fallback key] (deduped);
        empty when uncacheable. A connector that does not apply the
        pushdown gives every pushdown the same batches: ONE key, so
        that bindings that differ only in a literal bound share one
        resident copy of the split."""
        if not cacheable:
            return []
        static = static_pushdown
        if not applies_pushdown:
            pushdown = static = None
        try:
            keys = [ScanCache.key(conn, catalog, split, columns,
                                  pushdown, version, rows_per_batch)]
            if _freeze(static) != _freeze(pushdown):
                keys.append(ScanCache.key(conn, catalog, split, columns,
                                          static, version,
                                          rows_per_batch))
            return keys
        except TypeError:
            return []            # unhashable connector payload

    def stage(b: Batch) -> Batch:
        if pad is not None:
            tgt = pad.target(b.capacity)
            if tgt > b.capacity:
                from ..ops.jitcache import pad_capacity_jit
                b = pad_capacity_jit(b, tgt)
        # start the host->device transfer from the producing thread so
        # it overlaps the consumer's kernels (no-op for resident arrays)
        b = jax.device_put(b)
        if opts.prefetch:
            # only batches the background pipeline actually staged
            # count — the serial path must not inflate the A/B metric
            _PREFETCH_BATCHES.inc()
        return b

    def replay(i: int, split, cached, t0: float) -> Iterator[Batch]:
        if stats is not None:
            stats.record_cache(True)
        for b in cached:
            if pad is not None:
                pad.target(b.capacity)
            yield b
        if record_split is not None:
            record_split(i, t0, len(cached))

    def attach_wait(fl: "_InFlight") -> bool:
        """Wait on another query's in-flight decode of this split
        (shared-scan batching). True when its batches are usable. The
        wait is an input stall: observed and credited back to the fair
        scheduler like a prefetch stall."""
        from . import taskexec
        _SHARED_ATTACH.inc()
        t_stall = time.perf_counter()
        deadline = t_stall + SHARED_WAIT_S
        done = True
        while not fl.event.wait(0.1):
            if check_cancel is not None:
                check_cancel()
            if time.perf_counter() > deadline:
                done = False      # wedged producer: decode solo
                break
        dt = time.perf_counter() - t_stall
        _STALL.observe(dt)
        taskexec.GLOBAL.note_stall(dt)
        if stats is not None:
            stats.prefetch_stall_s += dt
        mfl = _flight.current_flight()
        if mfl is not None:
            mfl.record("stall", wall=dt)
        return done and fl.batches is not None

    def split_batches(i: int, split) -> Iterator[Batch]:
        t0 = time.perf_counter()
        pushdown = pushdown_fn()
        keys = split_keys(split, pushdown)
        owner_key = None
        solo = False
        while keys:
            cached = CACHE.get_any(keys, conn)
            if cached is not None:
                yield from replay(i, split, cached, t0)
                return
            if not opts.shared or solo:
                break
            fl, owner = CACHE.join_inflight(keys[0])
            if not owner and inline_scan:
                # another query owns the decode but THIS scan runs
                # inside its quantum: waiting would hold the device —
                # decode solo instead (duplicate work beats a stall)
                break
            if owner:
                # close the probe->register gap: a decode that started
                # and FINISHED between this query's miss and its
                # registration already inserted the entry — serve it
                # instead of decoding again
                cached = CACHE.get_any(keys, conn, count_miss=False)
                if cached is not None:
                    CACHE.finish_inflight(keys[0], cached)
                    yield from replay(i, split, cached, t0)
                    return
                owner_key = keys[0]
                break
            if attach_wait(fl):
                # ride the other query's decode: its staged batches
                # serve this consumer directly (put() may have been
                # refused by the memory limit — the list is live
                # either way)
                yield from replay(i, split, fl.batches, t0)
                return
            # producer failed/abandoned (event set, no batches): retry
            # the probe — this query may now become the owner. Producer
            # wedged past the wait budget (event unset): decode solo,
            # unregistered, so one stuck query cannot wedge its peers.
            solo = not fl.event.is_set()
        if keys and stats is not None:
            stats.record_cache(False)
        complete = None
        try:
            src = conn.page_source(split, list(columns),
                                   pushdown=pushdown,
                                   rows_per_batch=rows_per_batch)
            acc = [] if keys else None
            nb = 0
            batches = iter(src.batches())
            while True:
                # a miss: the connector generates or decodes the batch
                # and device_put stages it; one span a batch (a span
                # does not survive the yield below)
                with TRACER.task_span(trace_ctx, "scan-stage",
                                      table=split.table.table,
                                      split=i) as span:
                    b = next(batches, None)
                    if b is None:
                        break
                    # failpoint: abort mid-decode (chaos tests prove a
                    # failed/aborted scan never reaches the put() below
                    # — a partial column set must not become a resident
                    # cache entry)
                    FAILPOINTS.hit(
                        "scan.decode",
                        key=f"{catalog}.{split.table.table}.{i}",
                        split=i, batch=nb)
                    b = stage(b)
                    if TRACER.enabled:
                        span.annotate(bytes=batch_device_bytes(b))
                nb += 1
                if acc is not None:
                    acc.append(b)
                yield b
            if record_split is not None:
                record_split(i, t0, nb)
            if acc is not None:
                # only complete split streams insert: every early exit
                # above (decode error, failpoint, abort/GeneratorExit
                # from the consumer) skips this line by construction
                complete = acc
                CACHE.put(keys[0], conn, acc)
        finally:
            if owner_key is not None:
                # publish to attached queries on EVERY exit path: a
                # complete batch list serves them directly; None sends
                # them back to decode for themselves
                CACHE.finish_inflight(owner_key, complete)

    # serial warm fast path: splits already resident replay in order
    # with no thread/queue machinery at all; the pipeline spins up only
    # from the first cold split on (fully-warm queries — the repeated-
    # traffic case the cache exists for — never pay prefetch overhead)
    start = 0
    if cacheable:
        for i, split in enumerate(splits):
            t0 = time.perf_counter()
            keys = split_keys(split, pushdown_fn())
            cached = CACHE.get_any(keys, conn, count_miss=False) \
                if keys else None
            if cached is None:
                break                # split_batches re-probes, counted
            for b in replay(i, split, cached, t0):
                if check_cancel is not None:
                    check_cancel()
                yield b
            start = i + 1
        if start == len(splits):
            return
        splits = list(splits)[start:]

    if not opts.prefetch or opts.threads <= 1:
        for i, split in enumerate(splits, start):
            for b in split_batches(i, split):
                if check_cancel is not None:
                    check_cancel()
                yield b
        return

    # background prefetch: one bounded queue per split; the consumer
    # drains them in split order while workers decode+stage ahead of it
    DONE = object()
    stop = threading.Event()     # consumer gone (e.g. LIMIT satisfied)
    queues = [_queue.Queue(maxsize=max(1, opts.depth)) for _ in splits]
    pending: "_queue.Queue[int]" = _queue.Queue()
    for i in range(len(splits)):
        pending.put(i)

    def put(q, item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def worker() -> None:
        while not stop.is_set():
            try:
                i = pending.get_nowait()
            except _queue.Empty:
                return
            try:
                # ``start + i``: split numbering in stats stays global
                # even when the warm fast path served a prefix
                for b in split_batches(start + i, splits[i]):
                    if not put(queues[i], b):
                        return
            except BaseException as e:  # surfaced on the consumer side
                put(queues[i], e)
                return
            put(queues[i], DONE)

    n_workers = max(1, min(int(opts.threads), len(splits)))
    workers = [threading.Thread(target=worker, daemon=True,
                                name=f"scan-prefetch-{j}")
               for j in range(n_workers)]
    for w in workers:
        w.start()
    from . import taskexec
    try:
        for q in queues:
            while True:
                try:
                    item = q.get_nowait()
                except _queue.Empty:
                    # consumer outran the prefetcher: the wait is an
                    # input stall — observable, and credited back to
                    # the device scheduler (stalled != computing)
                    t_stall = time.perf_counter()
                    item = q.get()
                    dt = time.perf_counter() - t_stall
                    _STALL.observe(dt)
                    taskexec.GLOBAL.note_stall(dt)
                    if stats is not None:
                        stats.prefetch_stall_s += dt
                    mfl = _flight.current_flight()
                    if mfl is not None:
                        mfl.record("stall", wall=dt)
                if item is DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                if check_cancel is not None:
                    check_cancel()
                yield item
    finally:
        stop.set()
        for w in workers:
            # bounded join: workers notice ``stop`` within one 0.1s put
            # timeout; tests assert no scan-prefetch threads leak
            w.join(timeout=2.0)
