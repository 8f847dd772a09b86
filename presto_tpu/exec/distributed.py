"""Distributed plan executor: SPMD stages over a device mesh.

The TPU-native form of the reference's distributed execution stack
(reference presto-main/.../sql/planner/PlanFragmenter.java:106 splits the
plan at exchanges; execution/scheduler/SqlQueryScheduler.java:533 runs the
stage DAG; operator/PartitionedOutputOperator.java:48 +
operator/ExchangeClient.java implement the shuffle). Here:

- a worker's share of a stage is a SHARD of one SPMD program over the mesh
  axis, not a process: batches live as globally-sharded arrays
  (NamedSharding over "dp"), so elementwise stages (scan-filter-project)
  parallelize via GSPMD with zero collectives;
- exchanges are collectives inside shard_map: FIXED_HASH distribution is
  the quota-compacted all_to_all over ICI (repartition_by_hash_compact),
  FIXED_BROADCAST is a device-to-device all-gather of the build side,
  GATHER (final output / merge) is an all_gather; no operator stages
  batches through the host — sort/top-n/window/unnest run shard-local
  with one collective merge;
- aggregation splits into partial (shard-local) -> hash exchange -> final,
  exactly Presto's PARTIAL/FINAL AggregationNode split, but fused into one
  jitted program per stage instead of two tasks and a wire format.

Scan splits are assigned round-robin to shards (reference
execution/scheduler/UniformNodeSelector.java role); each chunk becomes one
globally-sharded batch with equal per-shard capacity.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import types as T
from ..batch import Batch, Column, Schema, bucket_capacity, concat_batches
from ..expr import ir
from ..expr.compiler import compile_filter, compile_projection
from ..obs import flight as _flight
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER, device_sync
from ..ops.aggregation import AggSpec, global_aggregate, grouped_aggregate
from ..ops.join import (
    build_match_mask, expand_join, lookup_join, match_count_max,
    semi_join_mask,
)
from ..ops.sort import SortKey, limit as limit_kernel, sort_batch, top_n
from ..parallel.exchange import partition_counts
from ..parallel.mesh import make_mesh
from ..planner.plan import (
    AggregationNode, DistinctNode, FilterNode, JoinNode, LimitNode,
    OutputNode, PlanNode, ProjectNode, SemiJoinNode, SortNode,
    TableScanNode, TopNNode, UnionNode, ValuesNode,
)
from ..planner.planner import LogicalPlan, Session, bool_property
from .local import QueryResult, _Executor, _plan_schema

#: mesh-path auto-selection observable: one count per query the router
#: placed on the SPMD substrate (the signal the default-on tests and
#: the MULTICHIP bench assert on)
_MESH_SELECTED = REGISTRY.counter("mesh_path_selected_total")
#: adaptive re-splits: one count per hot-bucket re-assignment a
#: _PartitionMap performed mid-query (StageMonitor's skew verdict
#: turned into action)
_MESH_RESPLITS = REGISTRY.counter("mesh_repartition_resplit_total")
#: host dispatches onto the mesh: one count per ``_smap`` program
#: invocation (the dotted tail labels the issuing stage kind). The
#: fused-exchange win is this counter's per-query delta shrinking ~3x+,
#: not just wall attribution — the MULTICHIP bench records the ratio
_MESH_DISPATCHES = REGISTRY.counter("mesh_dispatches_total")

#: cached 1-D meshes per device count (Mesh construction is cheap, but
#: a stable object keeps sharding identity stable across queries)
_MESH_CACHE: Dict[int, jax.sharding.Mesh] = {}

#: cross-query shard_map program cache: (call site, closure value
#: signature, specs, donate, mesh) -> _TimedEntry. A fresh executor per
#: query used to rebuild every jax.jit(shard_map(...)) object, so even
#: a WARM query paid a full re-trace per program — the last head of the
#: dispatch tax after the fused exchange removed the per-round one.
#: ops/jitcache.program_signature proves a closure only captures
#: value-stable state (plan nodes, schemas, key tuples, quotas); any
#: program it cannot prove keeps compile-per-query behavior. Bounded
#: LRU: assignment tuples from adaptive re-splits would otherwise grow
#: the cache without limit on a long-lived server.
_PROGRAM_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_PROGRAM_CACHE_CAP = 512
_PROGRAM_CACHE_LOCK = threading.Lock()
_PROGRAM_HITS = REGISTRY.counter("mesh_program_cache_hit_total")
_PROGRAM_MISSES = REGISTRY.counter("mesh_program_cache_miss_total")


class _FlightDispatch:
    """Wraps an ``_smap`` executable so every host-side dispatch counts
    in ``mesh_dispatches_total`` (dotted tail = issuing stage kind) and
    — when a flight recorder is active and ``kind`` is not None — lands
    as one flight round (obs/flight.py). Call semantics are
    untouched."""

    __slots__ = ("entry", "kind", "rounds", "_stage_counter")

    def __init__(self, entry, kind: Optional[str], stage: str = "misc",
                 rounds: int = 1):
        self.entry = entry
        self.kind = kind
        #: device exchange rounds one dispatch covers: a fused
        #: ``lax.fori_loop`` program amortizes R rounds behind a single
        #: host touch, and the flight record says so instead of
        #: undercounting the loop
        self.rounds = max(int(rounds), 1)
        self._stage_counter = REGISTRY.counter(
            f"mesh_dispatches_total.{stage}")

    def __call__(self, *args):
        _MESH_DISPATCHES.inc()
        self._stage_counter.inc()
        fl = _flight.current_flight()
        if fl is None or self.kind is None:
            return self.entry(*args)
        t0 = time.perf_counter()
        out = self.entry(*args)
        fl.record(self.kind, wall=time.perf_counter() - t0,
                  rounds=self.rounds)
        return out


def _batch_row_bytes(batch: Batch) -> int:
    """Rough per-row wire width (column storage + validity + mask) —
    sizes the flight recorder's bytes-moved estimate for an exchange
    round without touching device data."""
    return sum(c.data.dtype.itemsize + 1 for c in batch.columns) + 1


def _sync_record(what: str, launch, *args):
    """The host copy of the control scalar(s) ``launch(*args)`` returns:
    the program launched, then read through ``device_sync`` (its
    ``device-sync`` span opens once the launch is made). The whole
    interval, launch included, is ALSO one ``sync`` flight round (the
    control_sync bucket of the mesh attribution), so the executable
    dispatched here must be built with ``flight_kind=None``: its wall
    isn't counted twice."""
    fl = _flight.current_flight()
    t0 = time.perf_counter() if fl is not None else 0.0
    try:
        return device_sync(what, launch(*args))
    finally:
        if fl is not None:
            fl.record("sync", wall=time.perf_counter() - t0)


def _drain_inputs(*values) -> None:
    """Wait out the device arrays feeding a control-scalar fetch,
    recorded as a ``drain`` flight round (device_compute bucket). On an
    async backend the blocking wall at a ``_sync_record`` site is
    dominated by upstream compute still in flight — without this
    bracket that compute smears into ``control_sync`` exactly when the
    fused exchange shrinks the real control plane, and the bucket
    budgets gate on a lie. After the drain, the sync bracket times only
    the control round trip itself. The ``input-drain`` span is all
    ``wait_s`` (``device_sync`` with nothing to fetch)."""
    fl = _flight.current_flight()
    t0 = time.perf_counter() if fl is not None else 0.0
    try:
        device_sync("input-drain", [v for v in values if v is not None],
                    fetch=False)
    finally:
        if fl is not None:
            fl.record("drain", wall=time.perf_counter() - t0)


def mesh_mode(session) -> str:
    """Resolved ``mesh_execution`` mode: the session property when set,
    else the ``PRESTO_TPU_MESH_EXECUTION`` environment default, else
    ``auto`` (mesh whenever >1 device is visible and the plan cuts into
    mesh stages)."""
    v = session.properties.get("mesh_execution")
    if v is None:
        v = os.environ.get("PRESTO_TPU_MESH_EXECUTION", "auto")
    return str(v).lower()


def mesh_flight_on(session) -> bool:
    """Resolved ``mesh_flight`` switch: the session property when set,
    else the ``PRESTO_TPU_MESH_FLIGHT`` environment default, else on —
    the recorder is cheap enough (a counted cost, in tests) to fly every
    mesh query."""
    v = session.properties.get("mesh_flight")
    if v is None:
        return os.environ.get(
            "PRESTO_TPU_MESH_FLIGHT", "on").lower() \
            not in ("off", "0", "false")
    return bool(v)


def mesh_device_count(session) -> int:
    """Effective mesh width: every visible device, clamped by the
    ``mesh_devices`` session property when positive."""
    have = len(jax.devices())
    want = int(session.properties.get("mesh_devices", 0) or 0)
    return min(want, have) if want > 0 else have


def _walk_scans(node) -> Iterator[TableScanNode]:
    if isinstance(node, TableScanNode):
        yield node
    for c in node.children:
        yield from _walk_scans(c)


#: memoized router verdicts per LogicalPlan identity: the serving hot
#: path re-executes one cached plan thousands of times, and the
#: O(plan-size) fragmenter walk must run once per plan, not once per
#: query. Entries carry a weakref to the plan and only serve while it
#: still points at the same live object — id reuse after GC can never
#: resurrect a dead plan's verdict. Lock-guarded: concurrent serving
#: queries route through here on many threads (lockcheck: leaf lock,
#: never held across a dispatch).
_PLAN_VERDICTS: Dict[int, Tuple[object, Tuple[bool, bool, str]]] = {}
from .._devtools.lockcheck import checked_lock
_PLAN_VERDICTS_LOCK = checked_lock("distributed.plan_verdicts")


def _plan_mesh_verdict(plan: LogicalPlan) -> Tuple[bool, bool, str]:
    """(fragments-into-mesh-stages, reads-real-data, reason)."""
    import weakref
    key = id(plan)
    with _PLAN_VERDICTS_LOCK:
        hit = _PLAN_VERDICTS.get(key)
        if hit is not None and hit[0]() is plan:
            return hit[1]
    from ..planner.fragmenter import plan_mesh_stages
    roots = [plan.root] + list(plan.init_plans)
    supported, reason = True, ""
    for r in roots:
        mp = plan_mesh_stages(r)
        if not mp.supported:
            supported, reason = False, mp.reason
            break
    scans = [s for r in roots for s in _walk_scans(r)]
    scannable = bool(scans) and all(s.catalog != "system"
                                    for s in scans)
    verdict = (supported, scannable, reason)
    with _PLAN_VERDICTS_LOCK:
        if len(_PLAN_VERDICTS) > 512:
            # evict dead plans first, then oldest-inserted live ones —
            # never clear(): wiping live cached plans' verdicts would
            # re-run the fragmenter walk on exactly the hot path this
            # memo exists for
            for k in [k for k, (ref, _) in _PLAN_VERDICTS.items()
                      if ref() is None]:
                _PLAN_VERDICTS.pop(k, None)
            while len(_PLAN_VERDICTS) > 512:
                _PLAN_VERDICTS.pop(next(iter(_PLAN_VERDICTS)), None)
        _PLAN_VERDICTS[key] = (weakref.ref(plan), verdict)
    return verdict


def select_mesh(session: Session,
                plan: LogicalPlan) -> Optional[jax.sharding.Mesh]:
    """The mesh auto-router: the Mesh this query should execute on, or
    None for the single-device path. ``auto`` (the default) selects the
    mesh when more than one device is effective, the plan (init plans
    included) cuts into mesh stages (planner/fragmenter.plan_mesh_stages)
    and the query reads real data (system-catalog metadata queries gain
    nothing from SPMD); ``on`` forces the mesh — an unfragmentable plan
    then raises instead of silently degrading; ``off`` never meshes."""
    mode = mesh_mode(session)
    if mode == "off":
        return None
    n = mesh_device_count(session)
    if n < 2 and mode != "on":
        return None
    supported, scannable, reason = _plan_mesh_verdict(plan)
    if not supported:
        if mode == "on":
            raise NotImplementedError(
                f"mesh_execution=on: plan has no mesh form ({reason})")
        return None
    if mode != "on" and not scannable:
        return None
    mesh = _MESH_CACHE.get(n)
    if mesh is None:
        mesh = _MESH_CACHE[n] = make_mesh(max(n, 1))
    _MESH_SELECTED.inc()
    return mesh


#: bucket subdivisions per shard in the adaptive exchange: B = n*4
#: buckets give the greedy re-balancer ~25%-of-a-shard granularity
#: without growing the quota readback beyond a few hundred scalars
_RESPLIT_FACTOR = 4


def _skew_ratio() -> float:
    """One engine-wide definition of "skewed": the coordinator
    StageMonitor's verdict ratio (exec/cluster.py, PR 3) also decides
    when the mesh exchange re-splits hot buckets."""
    from .cluster import StageMonitor
    return float(StageMonitor.skew_ratio)


def _per_dest_quota(counts: np.ndarray, assign: Sequence[int],
                    n: int) -> int:
    """Max live rows any (src shard, dst shard) pair ships under
    ``assign``: the static quota the compacted exchange needs."""
    a = np.asarray(assign)
    worst = 1
    for d in range(n):
        sel = counts[:, a == d]
        if sel.size:
            worst = max(worst, int(sel.sum(axis=1).max()))
    return worst


class _PartitionMap:
    """Bucket -> shard assignment shared by every exchange of one
    operator. Both sides of a partitioned join ship through ONE map, so
    equal keys colocate under ANY assignment (keys hash to buckets,
    buckets move atomically). The map observes per-bucket live counts
    as batches flow and re-splits hot buckets between batches: when one
    shard's load crosses the StageMonitor skew ratio over the median
    shard and a greedy LPT re-balance of bucket totals actually lowers
    the max, the assignment flips, ``epoch`` bumps, and the owning
    operator re-ships its prepared side under the new map."""

    #: re-balancing converges or it stops — never thrash the build side
    MAX_CHANGES = 2

    def __init__(self, n: int, adaptive: bool = True,
                 ratio: Optional[float] = None):
        self.n = n
        self.buckets = n * _RESPLIT_FACTOR
        self.assign: Tuple[int, ...] = tuple(
            b % n for b in range(self.buckets))
        self.epoch = 0
        self.adaptive = bool(adaptive) and n > 1
        self.ratio = float(ratio) if ratio is not None else _skew_ratio()
        self.changes = 0
        self._totals = np.zeros(self.buckets, dtype=np.int64)

    def observe(self, counts: np.ndarray) -> None:
        """Fold one batch's [n_src, buckets] live counts in; maybe
        re-assign."""
        if not self.adaptive:
            return
        t0 = time.perf_counter()
        self._totals += counts.sum(axis=0, dtype=np.int64)
        if self.changes >= self.MAX_CHANGES:
            return
        loads = np.zeros(self.n, dtype=np.int64)
        np.add.at(loads, np.asarray(self.assign), self._totals)
        # skew verdict against the BALANCED load (total/n), not the
        # median: with most shards idle the median collapses to zero
        # and a median test would never fire exactly when it matters
        fair = float(self._totals.sum()) / self.n
        if fair < 1.0 or float(loads.max()) <= self.ratio * fair:
            return
        new = self._greedy()
        new_loads = np.zeros(self.n, dtype=np.int64)
        np.add.at(new_loads, np.asarray(new), self._totals)
        if new == self.assign or new_loads.max() >= loads.max():
            return            # a single hot KEY cannot be split further
        self.assign = new
        self.epoch += 1
        self.changes += 1
        _MESH_RESPLITS.inc()
        fl = _flight.current_flight()
        if fl is not None:
            fl.record("resplit", wall=time.perf_counter() - t0,
                      rows=int(self._totals.sum()),
                      loads=[int(x) for x in new_loads])

    def _greedy(self) -> Tuple[int, ...]:
        """LPT: heaviest bucket first onto the least-loaded shard."""
        order = np.argsort(-self._totals, kind="stable")
        loads = [0] * self.n
        out = [0] * self.buckets
        for b in order:
            d = min(range(self.n), key=lambda i: (loads[i], i))
            out[int(b)] = d
            loads[d] += int(self._totals[int(b)])
        return tuple(out)


#: deferred skew checks in the fused exchange: device-side bucket
#: counts are fetched and folded into the _PartitionMap once per this
#: many rounds (minus the in-flight newest — see observe_pending), so
#: the host control plane touches the device once per stage-ish instead
#: of once per round and re-splits become a rarer loop-exit path
_FUSED_OBSERVE_EVERY = 4

#: per-shard slot ceiling for the fused aggregation carry — a grouping
#: only rides the multi-round fori_loop when its dense key domain proves
#: the state fits this many slots on every round (the PR 2/PR 10
#: stats-bounded-capacity contract applied to loop-invariant shapes)
_FUSED_STATE_SLOTS = 1 << 15
#: gathered-state ceiling (global rows) under which the fused finisher
#: replaces the hash-exchange + final pair with ONE all-gather + final
#: dispatch, masking all but shard 0 (the _global_agg pattern)
_FUSED_GATHER_SLOTS = 1 << 17


class _Repartitioner:
    """Quota-compacted bucket-hash exchange driver, two control planes:

    - **fused** (default, ``mesh_fused_exchange``): bucket-count + ship
      run as ONE collective program per round (exchange.
      repartition_fused) under a capacity-safe static quota, so a round
      is a single dispatch with no quota readback. Per-bucket counts
      ride along as a device-resident second output; the host folds
      them into the shared _PartitionMap only at deferred observe
      points (builds force one; probe loops check every
      _FUSED_OBSERVE_EVERY rounds, lagging one round so the fetch never
      blocks on an in-flight dispatch) — control scalars once per
      stage, re-splits preserved as a rarer loop-exit-and-rebuild path.
    - **classic** (escape hatch / tight-wire callers): one cheap
      collective reads per-(src, bucket) live counts, the host sizes
      the static quota and may re-balance hot buckets, and the exchange
      ships exactly quota slots per peer (wire cost ~C instead of the
      masked all_to_all's n*C; reference operator/
      PartitionedOutputOperator.java PagePartitioner).

    Jitted exchanges are cached per (assignment, quota bucket)."""

    def __init__(self, ex: "DistributedExecutor",
                 key_cols: Sequence[int], pmap: _PartitionMap,
                 fused: Optional[bool] = None):
        self.ex = ex
        self.keys = tuple(key_cols)
        self.map = pmap
        self.fused = (ex.fused_exchange if fused is None else bool(fused))
        self._counts_fn = None
        self._fns: Dict[Tuple, object] = {}
        self._fused_fns: Dict[Tuple, object] = {}
        self._last_counts: Optional[np.ndarray] = None
        #: device-resident [n*buckets] count vectors awaiting observe
        self._pending: List[object] = []
        self._rounds_since_observe = 0

    @property
    def epoch(self) -> int:
        return self.map.epoch

    def _counts(self, batch: Batch) -> np.ndarray:
        if self._counts_fn is None:
            self._counts_fn = self.ex._smap(
                lambda b, _k=self.keys, _bk=self.map.buckets:
                partition_counts(b, _k, _bk), 1,
                flight_kind=None, stage="exchange")
        _drain_inputs(batch)
        raw = np.asarray(_sync_record(
            "exchange-quota", self._counts_fn, batch))
        return raw.reshape(self.ex.n, self.map.buckets)

    # -- fused control plane --------------------------------------------------
    def fused_quota(self, batch: Batch) -> int:
        """Capacity-safe static quota: any per-(src, dst) live count is
        bounded by the source shard's lane count, so this quota can
        never drop a row and needs no counts readback."""
        return bucket_capacity(max(batch.capacity // self.ex.n, 1))

    def note_counts(self, counts, rows_hint: int = 0) -> None:
        """Queue one fused round's device-side bucket counts for a
        deferred skew check (and keep the exchange-round metrics
        continuous with the classic plane)."""
        REGISTRY.counter("exchange_repartitions_total").inc()
        if not self.map.adaptive:
            return
        self._pending.append(counts)
        self._rounds_since_observe += 1
        if self._rounds_since_observe >= _FUSED_OBSERVE_EVERY:
            # pipelined check: leave the newest round's counts pending
            # so the device_get only touches rounds that already
            # retired — the fetch never stalls on in-flight compute
            self.observe_pending(keep_newest=len(self._pending) > 1)

    def observe_pending(self, keep_newest: bool = False) -> None:
        """Fetch queued device counts ONCE and fold them into the
        shared _PartitionMap — the per-stage control-scalar sync of the
        fused plane (builds call this; probe loops hit it every
        _FUSED_OBSERVE_EVERY rounds)."""
        take = self._pending[:-1] if keep_newest else self._pending
        if not take:
            return
        self._pending = self._pending[-1:] if keep_newest else []
        self._rounds_since_observe = len(self._pending)
        total = np.zeros((self.ex.n, self.map.buckets), dtype=np.int64)
        _drain_inputs(*take)
        for c in _sync_record("exchange-skew-check", lambda: take):
            total += np.asarray(c).reshape(self.ex.n, self.map.buckets)
        self._last_counts = total
        self.map.observe(total)

    def _fused_ship(self, batch: Batch,
                    record_counts: bool = True) -> Batch:
        from .failpoints import FAILPOINTS
        fl = _flight.current_flight()
        t0 = time.perf_counter()
        FAILPOINTS.hit("mesh.repartition")
        assign = self.map.assign
        quota = self.fused_quota(batch)
        key = (assign, quota)
        fn = self._fused_fns.get(key)
        if fn is None:
            from ..parallel.exchange import repartition_fused
            fn = self._fused_fns[key] = self.ex._smap(
                lambda b, _k=self.keys, _ax=self.ex.axis,
                _n=self.ex.n, _a=assign, _q=quota: repartition_fused(
                    b, _k, _ax, _n, _a, _q), 1,
                n_out=2, flight_kind=None, stage="exchange")
        out, counts = fn(batch)
        if record_counts:
            self.note_counts(counts)
        else:
            # replay rounds still SHIP (the exchange-round ledger stays
            # whole) — they just don't fold counts in twice
            REGISTRY.counter("exchange_repartitions_total").inc()
        if fl is not None:
            # one record per fused exchange round; the failpoint rides
            # inside the timed span exactly like the classic _ship (row
            # loads stay device-resident — that's the point)
            fl.record("repartition", wall=time.perf_counter() - t0)
        return out

    def _ship(self, batch: Batch, counts: np.ndarray) -> Batch:
        from .failpoints import FAILPOINTS
        fl = _flight.current_flight()
        t0 = time.perf_counter()
        FAILPOINTS.hit("mesh.repartition")
        assign = self.map.assign
        quota = bucket_capacity(
            _per_dest_quota(counts, assign, self.ex.n))
        key = (assign, quota)
        fn = self._fns.get(key)
        if fn is None:
            from ..parallel.exchange import repartition_by_buckets_compact
            fn = self._fns[key] = self.ex._smap(
                lambda b, _k=self.keys, _ax=self.ex.axis,
                _n=self.ex.n, _a=assign, _q=quota:
                repartition_by_buckets_compact(
                    b, _k, _ax, _n, _a, _q), 1,
                flight_kind=None, stage="exchange")
        REGISTRY.counter("exchange_repartitions_total").inc()
        out = fn(batch)
        if fl is not None:
            # per-dest row loads under the CURRENT assignment: the
            # round's straggler signal for the critical path
            loads = np.zeros(self.ex.n, dtype=np.int64)
            np.add.at(loads, np.asarray(assign),
                      counts.sum(axis=0, dtype=np.int64))
            rows = int(loads.sum())
            fl.record("repartition", wall=time.perf_counter() - t0,
                      rows=rows, nbytes=rows * _batch_row_bytes(batch),
                      loads=[int(x) for x in loads])
        return out

    def __call__(self, batch: Batch) -> Batch:
        if self.fused:
            return self._fused_ship(batch)
        counts = self._counts(batch)
        self._last_counts = counts
        self.map.observe(counts)
        return self._ship(batch, counts)

    def replay(self, batch: Batch) -> Batch:
        """Re-ship a batch this exchange already observed (the join's
        build side after a probe-driven re-split) under the CURRENT
        assignment, without folding its counts in twice."""
        if self.fused:
            return self._fused_ship(batch, record_counts=False)
        counts = (self._last_counts if self._last_counts is not None
                  else self._counts(batch))
        return self._ship(batch, counts)


class DistributedExecutor(_Executor):
    """Executes a logical plan with data sharded over a mesh axis.

    Inherits the streaming structure of the local executor; overrides the
    exchange-bearing nodes (scan placement, aggregation, join, semi join,
    sort/top-n/distinct finalization) with SPMD implementations.
    """

    compact_streams = False   # compact() on a mesh-sharded batch would
    #                            gather it across devices; shard-local
    #                            compaction happens in the exchange path

    def __init__(self, session: Session, rows_per_batch: int,
                 mesh: jax.sharding.Mesh, stats=None):
        super().__init__(session, rows_per_batch, stats=stats)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n = mesh.shape[self.axis]
        self._row_sharding = NamedSharding(mesh, P(self.axis))
        self._replicated = NamedSharding(mesh, P())
        #: memoized all-gather identity (see _replicate_device): one
        #: trace per executor, not one per broadcast build side
        self._replicate_jit = None
        #: fused SPMD exchange (default on): counts + ship collapse
        #: into one collective program per round, stats-bounded stages
        #: loop multiple rounds inside one dispatch, and control
        #: scalars are fetched once per stage. mesh_fused_exchange=off
        #: is the escape hatch back to the per-round host control plane
        self.fused_exchange = bool_property(session, "mesh_fused_exchange",
                                            True)
        #: cap on chunks one fused lax.fori_loop dispatch may stack
        #: (bounds resident memory: the stacked wave holds every chunk)
        try:
            self.fused_loop_rounds = max(int(
                session.properties.get("mesh_fused_loop_rounds", 32)), 1)
        except (TypeError, ValueError):
            self.fused_loop_rounds = 32

    # -- sharding helpers ----------------------------------------------------
    def _shard_rows(self, batch: Batch) -> Batch:
        """Place a host-built batch row-sharded across the mesh."""
        put = lambda x: jax.device_put(x, self._row_sharding)
        cols = [Column(c.type, put(c.data), put(c.validity), c.dictionary)
                for c in batch.columns]
        return Batch(batch.schema, cols, put(batch.row_mask))

    def _smap(self, fn, n_in: int, replicated_in: Sequence[int] = (),
              n_out: int = 1, replicated_out=False,
              flight_kind: Optional[str] = "dispatch",
              stage: str = "misc", donate: Sequence[int] = (),
              rounds: int = 1):
        in_specs = tuple(
            P() if i in replicated_in else P(self.axis)
            for i in range(n_in))
        # replicated_out: every shard computes the identical value (e.g.
        # preparing a replicated build side), so the output stays P() —
        # specs are PREFIX pytrees, so one spec covers a whole prepared
        # tuple of arrays. True replicates every output; a sequence
        # names the replicated output POSITIONS (a fused program can
        # ship a sharded batch plus a replicated control scalar)
        if isinstance(replicated_out, bool):
            rep_out = (set(range(n_out)) if replicated_out else set())
        else:
            rep_out = set(replicated_out)
        out_specs = ((P() if 0 in rep_out else P(self.axis))
                     if n_out == 1
                     else tuple(P() if i in rep_out else P(self.axis)
                                for i in range(n_out)))
        # registered entry, not a raw jax.jit: every shard_map program
        # is an executable like any jitcache kernel — compiles and
        # (profiled) device time land in obs.profiler.EXECUTABLES
        # instead of being invisible to the PR 6 cost plane. The static
        # key is the defining CALL SITE (code object) + specs:
        # anonymous lambdas from different sites must not collapse into
        # one 'smap:<lambda>' record (that would sum unrelated
        # operators' compiles/FLOPs into one executables row), while
        # re-builds of the same program share one record instead of
        # churning the registry query after query
        from ..ops.jitcache import (program_name, program_signature,
                                    timed_entry)
        label = getattr(fn, "__qualname__", None) \
            or getattr(fn, "__name__", "fn")
        code = getattr(fn, "__code__", None)
        site = ((code.co_filename, code.co_firstlineno)
                if code is not None else id(fn))
        donate = tuple(donate)
        # cross-query reuse: when the closure's captured state is
        # provably value-stable, the SAME jitted program serves every
        # query with this shape — warm queries skip the re-trace that
        # used to dominate their dispatch wall (jax.jit's own trace
        # cache keys on the function OBJECT, so rebuilding the object
        # per query forfeited it)
        sig = program_signature(fn)
        cache_key = None
        entry = None
        if sig is not None:
            cache_key = (site, sig, in_specs, out_specs, donate,
                         self.axis, tuple(self.mesh.devices.flat))
            with _PROGRAM_CACHE_LOCK:
                entry = _PROGRAM_CACHE.get(cache_key)
                if entry is not None:
                    _PROGRAM_CACHE.move_to_end(cache_key)
            (_PROGRAM_HITS if entry is not None
             else _PROGRAM_MISSES).inc()
        if entry is None:
            entry = timed_entry(
                f"smap:{label.split('.<locals>.')[-1]}",
                shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False),
                (site, in_specs, out_specs, donate), donate=donate,
                program=program_name("smap", "_".join(
                    [stage] + [c for c in label.split(".")
                               if c != "<locals>"][-2:])))
            if cache_key is not None:
                with _PROGRAM_CACHE_LOCK:
                    _PROGRAM_CACHE[cache_key] = entry
                    while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_CAP:
                        _PROGRAM_CACHE.popitem(last=False)
        # flight recorder: each dispatch is one round record (kind
        # "dispatch" -> dispatch_overhead; "repartition" for exchange
        # fns; None when the caller brackets the call in _sync_record —
        # every variant still counts in mesh_dispatches_total)
        return _FlightDispatch(entry, flight_kind, stage=stage,
                               rounds=rounds)

    def _shard_live_max(self, batch: Batch) -> int:
        """Max live rows on any shard (host sync) — sizes compactions."""
        per = self._smap(
            lambda b: jnp.sum(b.row_mask, keepdims=True).astype(jnp.int64), 1,
            flight_kind=None)
        _drain_inputs(batch)
        counts = np.asarray(_sync_record("shard-live-max", per, batch))
        return int(counts.max()) if counts.size else 0

    def _replicate_device(self, batch: Batch) -> Batch:
        """Re-shard a row-sharded batch to fully-replicated WITHOUT a host
        round trip: jit identity with replicated output sharding makes XLA
        insert the all-gather over ICI (the FIXED_BROADCAST exchange,
        reference operator/ExchangeClient.java pulling a broadcast buffer —
        here device-to-device only)."""
        fn = self._replicate_jit
        if fn is None:
            from ..ops.jitcache import timed_entry
            fn = self._replicate_jit = _FlightDispatch(timed_entry(
                "replicate_device", lambda b: b,
                out_shardings=self._replicated),
                "dispatch", stage="exchange")
        return fn(batch)

    def _repartitioner(self, key_cols: Sequence[int],
                       pmap: Optional[_PartitionMap] = None,
                       adaptive: bool = True,
                       fused: Optional[bool] = None) -> _Repartitioner:
        """An adaptive quota-compacted hash exchange (see
        :class:`_Repartitioner`). Pass one shared ``pmap`` for every
        exchange whose outputs must colocate (both sides of a
        partitioned join); single-shot exchanges get their own map.
        ``fused=False`` forces the classic counts-then-ship plane (a
        caller shipping a huge batch once may prefer the tight quota
        over saving one sync)."""
        if pmap is None:
            pmap = _PartitionMap(self.n, adaptive=adaptive)
        return _Repartitioner(self, key_cols, pmap, fused=fused)

    # -- scan: split placement ------------------------------------------------
    def _TableScanNode(self, node: TableScanNode) -> Iterator[Batch]:
        """Round-robin split streams across shards THROUGH the device
        scan cache + async prefetch pipeline (exec/scancache.py): each
        shard's stream is a cached ``scan_splits`` pipeline, so hot
        split data replays device-resident across mesh queries instead
        of re-decoding per query, cold splits decode/stage on
        background threads ahead of the mesh program, and hits/misses
        land on the same ``scan_cache_*`` observables as the local
        path. Per-round shard chunks stack into one globally-sharded
        batch — device-to-device when every chunk is resident
        (_assemble's composed path), through the host otherwise."""
        import time as _time

        from . import scancache

        conn = self.session.catalogs.get(node.catalog)
        opts = scancache.options_from_session(self.session)
        splits = conn.split_manager.splits(node.table, self.n)
        pushdown = node.pushdown or None
        t_query0 = _time.perf_counter()

        def record_for(shard: int):
            def record_split(i: int, t0: float, batches: int) -> None:
                if self.stats is not None:
                    self.stats.record_split(
                        node.table.table, shard, t0 - t_query0,
                        _time.perf_counter() - t0, batches)
            return record_split

        streams: List[Iterator[Batch]] = [
            scancache.scan_splits(
                conn, node.catalog, list(node.columns), [s],
                lambda: pushdown, self.rows_per_batch, opts,
                record_split=record_for(i),
                check_cancel=self._check_cancel, stats=self.stats,
                static_pushdown=pushdown)
            for i, s in enumerate(splits)
        ]
        while len(streams) < self.n:
            streams.append(iter(()))
        done = [False] * self.n
        while not all(done):
            fl = _flight.current_flight()
            t0 = _time.perf_counter()
            s0 = fl.kind_wall("stall") if fl is not None else 0.0
            parts: List[Optional[Batch]] = []
            for i, st in enumerate(streams):
                if done[i]:
                    parts.append(None)
                    continue
                try:
                    parts.append(next(st))
                except StopIteration:
                    done[i] = True
                    parts.append(None)
            if all(p is None for p in parts):
                break
            if fl is not None:
                # host scan work feeding the mesh: the pull wall minus
                # the prefetch stalls recorded INSIDE the pulls (those
                # already landed in the stall bucket)
                dt = (_time.perf_counter() - t0
                      - (fl.kind_wall("stall") - s0))
                fl.record("staging", wall=max(dt, 0.0))
            yield self._assemble(parts, _plan_schema(node))

    def _assemble_resident(self, parts: List[Optional[Batch]],
                           schema: Schema, cap: int) -> Optional[Batch]:
        """Stack per-shard device chunks into one globally-sharded batch
        WITHOUT a host round trip: pad each chunk to the round's bucket
        on device, copy it device-to-device onto its shard, and compose
        the global array from the per-shard pieces
        (jax.make_array_from_single_device_arrays). Returns None — and
        the caller falls back to host staging (_stage_parts) — when
        shards disagree on a dictionary (vocab merge needs the host) or
        the backend refuses the composition."""
        compose = getattr(jax, "make_array_from_single_device_arrays",
                          None)
        if compose is None:
            return None
        ncols = len(schema)
        vocabs: List[Optional[Tuple[str, ...]]] = []
        for ci in range(ncols):
            vs = {p.columns[ci].dictionary for p in parts
                  if p is not None
                  and p.columns[ci].dictionary is not None}
            if len(vs) > 1:
                return None
            vocabs.append(next(iter(vs)) if vs
                          else (() if schema.types[ci].is_string
                                else None))
        from ..ops.jitcache import pad_capacity_jit
        devs = list(self.mesh.devices.flat)
        padded: List[Optional[Batch]] = []
        for i in range(self.n):
            p = parts[i] if i < len(parts) else None
            if p is not None and p.capacity < cap:
                p = pad_capacity_jit(p, cap)
            padded.append(p)
        try:
            def compose_col(ci: int, which: str):
                proto = next(getattr(p.columns[ci], which)
                             for p in padded if p is not None)
                shards = []
                for i, p in enumerate(padded):
                    a = (getattr(p.columns[ci], which)
                         if p is not None
                         else jnp.zeros(proto.shape, proto.dtype))
                    shards.append(jax.device_put(a, devs[i]))
                shape = (self.n * cap,) + tuple(proto.shape[1:])
                return compose(shape, self._row_sharding, shards)

            cols = [Column(schema.types[ci], compose_col(ci, "data"),
                           compose_col(ci, "validity"), vocabs[ci])
                    for ci in range(ncols)]
            mask = compose(
                (self.n * cap,), self._row_sharding,
                [jax.device_put(
                    p.row_mask if p is not None
                    else jnp.zeros((cap,), dtype=bool), devs[i])
                 for i, p in enumerate(padded)])
            return Batch(schema, cols, mask)
        except Exception:
            return None          # any residency surprise: host staging

    def _assemble(self, parts: List[Optional[Batch]],
                  schema: Schema) -> Batch:
        """Stack per-shard batches into one globally-sharded batch —
        device-resident when possible, staged through the host when a
        vocab merge or backend limitation forces it."""
        cap = max(p.capacity for p in parts if p is not None)
        resident = self._assemble_resident(parts, schema, cap)
        if resident is not None:
            return resident
        ncols = len(schema)
        datas: List[List[np.ndarray]] = [[] for _ in range(ncols)]
        valids: List[List[np.ndarray]] = [[] for _ in range(ncols)]
        masks: List[np.ndarray] = []
        vocabs: List[Optional[Tuple[str, ...]]] = [None] * ncols
        self._stage_parts(parts, schema, cap, datas, valids,
                          masks, vocabs)
        cols = []
        for ci in range(ncols):
            data = np.concatenate(datas[ci])
            valid = np.concatenate(valids[ci])
            cols.append(Column(
                schema.types[ci],
                jax.device_put(data, self._row_sharding),
                jax.device_put(valid, self._row_sharding),
                vocabs[ci]))
        mask = jax.device_put(np.concatenate(masks), self._row_sharding)
        return Batch(schema, cols, mask)

    def _stage_parts(self, parts, schema: Schema, cap: int,
                     datas, valids, masks, vocabs) -> None:
        """Fetch every shard's columns to the host: staging
        deliberately rounds through the host to stack per-shard chunks —
        ONE ``device_sync`` reads the whole round so the stall is
        observable."""
        ncols = len(schema)
        fl = _flight.current_flight()
        t0 = time.perf_counter()
        from ..batch import unify_dictionaries
        fetched = iter(device_sync("scan-stage", [
            ([(c.data, c.validity) for c in p.columns], p.row_mask)
            for p in parts if p is not None]))
        for p in parts:
            if p is None:
                for ci in range(ncols):
                    dt = schema.types[ci].storage_dtype
                    datas[ci].append(np.zeros(cap, dtype=np.dtype(dt)))
                    valids[ci].append(np.zeros(cap, dtype=bool))
                masks.append(np.zeros(cap, dtype=bool))
                continue
            cols, m = next(fetched)
            for ci, (c, (d, v)) in enumerate(zip(p.columns, cols)):
                d, v = np.asarray(d), np.asarray(v)
                if c.dictionary is not None:
                    if vocabs[ci] is None:
                        vocabs[ci] = c.dictionary
                    elif vocabs[ci] != c.dictionary:
                        # remap codes into the accumulated vocabulary
                        merged, remaps = unify_dictionaries([
                            _host_col(c.type, vocabs[ci]),
                            c])
                        vocabs[ci] = merged
                        # remap previously collected shards
                        prev_map = remaps[0]
                        datas[ci] = [
                            _apply_remap(a, prev_map) for a in datas[ci]]
                        d = _apply_remap(d, remaps[1])
                pad = cap - d.shape[0]
                if pad:
                    d = np.pad(d, (0, pad))
                    v = np.pad(v, (0, pad))
                datas[ci].append(d)
                valids[ci].append(v)
            m = np.asarray(m)
            if cap - m.shape[0]:
                m = np.pad(m, (0, cap - m.shape[0]))
            masks.append(m)
        if fl is not None:
            loads = [int(m.sum()) for m in masks]
            nbytes = (sum(a.nbytes for lst in datas for a in lst)
                      + sum(a.nbytes for lst in valids for a in lst)
                      + sum(m.nbytes for m in masks))
            fl.record("staging", wall=time.perf_counter() - t0,
                      rows=sum(loads), nbytes=nbytes, loads=loads)

    def _ValuesNode(self, node: ValuesNode) -> Iterator[Batch]:
        for b in super()._ValuesNode(node):
            yield self._pad_shardable(b)

    def _pad_shardable(self, b: Batch) -> Batch:
        cap = b.capacity
        per = -(-cap // self.n)
        if per * self.n != cap:
            b = concat_batches([b], capacity=per * self.n)
        return self._shard_rows(b)

    # -- aggregation: partial -> hash exchange -> final -----------------------
    def _AggregationNode(self, node: AggregationNode) -> Iterator[Batch]:
        for a in node.aggs:
            if a.distinct:
                raise NotImplementedError(
                    "DISTINCT aggregates must be lowered by the planner")
        aggs = [AggSpec(a.fn, a.arg, a.output_type, a.name, mask=a.mask,
                        param=a.param)
                for a in node.aggs]
        group = list(node.group_indices)
        from ..ops.aggregation import percentile_drains
        # final-step nodes consume STATE columns whose layout the raw
        # agg input indices don't describe — never re-check them
        if node.step != "final" and \
                percentile_drains(aggs, _plan_schema(node.child).types,
                                  bool(group)):
            # approx_percentile: colocate each group's raw rows via hash
            # exchange, then one exact segmented-sort pass per shard (no
            # mergeable state exists — the window-node pattern)
            b = self._drain(node.child)
            if b is None:
                if group:
                    return
                b = self._pad_shardable(Batch.from_arrays(
                    _plan_schema(node.child),
                    [[] for _ in node.child.fields], num_rows=0))
            if group:
                b = self._repartitioner(group, fused=False)(b)
                fn = self._smap(
                    lambda x: grouped_aggregate(x, group, aggs,
                                                mode="single"), 1, stage="agg")
                yield fn(b)
            else:
                fn = self._smap(
                    lambda x: global_aggregate(
                        _gathered(x, self.axis), aggs, mode="single"), 1, stage="agg")
                yield _keep_first_shard(fn(b), self.n)
            return
        if not group:
            yield self._global_agg(node, aggs)
            return
        key_idx = list(range(len(group)))
        allow_dense = bool_property(self.session, "dense_grouping", True)
        kb = tuple(node.key_bounds) if node.key_bounds else None
        # fragment steps (the optimizer's eager-aggregation rewrite
        # pre-splits some aggregations): PARTIAL consumes raw rows and
        # yields shard-local state, FINAL consumes state rows, SINGLE
        # does both — same kernels, different sides of the state
        # boundary (mirrors exec/local.py _AggregationNode)
        step = node.step

        partial_fn = self._smap(
            lambda b: grouped_aggregate(b, group, aggs, mode="partial",
                                        key_bounds=kb,
                                        allow_dense=allow_dense), 1, stage="agg")
        merge_fn = None

        state: Optional[Batch] = None
        fused_state = False
        src: Iterator[Batch] = iter(self.run(node.child))
        if self.fused_exchange and allow_dense and step != "final":
            # fused control plane: drain chunks through multi-round
            # lax.fori_loop wave programs (one dispatch per wave, donated
            # carry, zero mid-stage syncs). Falls back to the classic
            # per-chunk loop below for whatever the drain did not take
            # (gate failed, or the wave signature changed mid-stream).
            state, src = self._fused_agg_drain(src, group, aggs, kb)
            fused_state = state is not None
        merges = 0
        next_check = 1
        check_every = 1
        for chunk in src:
            if kb is not None and allow_dense and step != "final":
                # sharded batches reduce to one replicated scalar; the
                # flag joins the query's single end-of-run error sync.
                # UNCONDITIONAL on this tier: per-shard dispatch depends
                # on post-exchange quota capacities the host can't
                # mirror, so bounds are enforced as hard invariants —
                # an overclaimed bound fails LOUDLY here rather than
                # risking a silent clamp in a later merge/final shard
                from ..ops.jitcache import key_bounds_violation_jit
                self.error_flags.append(
                    key_bounds_violation_jit(chunk, group, kb))
            partial = (chunk if step == "final" else partial_fn(chunk))
            if state is None:
                state = partial
            else:
                if merge_fn is None:
                    merge_fn = self._smap(
                        lambda a, b: grouped_aggregate(
                            concat_batches([a, b]), key_idx, aggs,
                            mode="merge", key_bounds=kb,
                            allow_dense=allow_dense), 2, stage="agg")
                merged = merge_fn(state, partial)
                merges += 1
                # compaction sizing is an optimization, never a
                # correctness gate (skipping a check only retains a
                # larger capacity for longer), so the live-max host
                # sync runs on a doubling cadence — first merge, back
                # off while nothing compacts, snap back when one does
                # (the local executor's adaptive sparse-check idiom)
                if merges >= next_check:
                    live = self._shard_live_max(merged)
                    cap = bucket_capacity(max(live, 1))
                    if cap * self.n < merged.capacity:
                        compact_fn = self._smap(
                            lambda b, _cap=cap: b.compact(_cap, check=False),
                            1, stage="agg")
                        merged = compact_fn(merged)
                        check_every = 1
                    else:
                        check_every = min(check_every * 2, 8)
                    next_check = merges + check_every
                state = merged
        if state is None:
            if node.default_gids and step in ("single", "final"):
                # grouping sets over empty input: synthesize the empty
                # sets' grand-total rows (see local._default_grouping_batch)
                from .local import _default_grouping_batch
                yield self._pad_shardable(_default_grouping_batch(node))
            return
        if step == "partial":
            # states stay shard-local: the downstream FINAL node owns
            # the hash exchange that co-locates groups
            yield state
            return
        if fused_state and state.capacity <= _FUSED_GATHER_SLOTS:
            # fused finisher: the carry's proven capacity is small
            # enough to all-gather, so the final runs replicated in ONE
            # dispatch — no exchange round at all. Output identical on
            # every shard; mask all but shard 0 (the _global_agg form)
            final_fn = self._smap(
                lambda b, _ax=self.axis: grouped_aggregate(
                    _gathered(b, _ax), key_idx, aggs, mode="final",
                    key_bounds=kb, allow_dense=allow_dense), 1,
                stage="agg")
            out = _keep_first_shard(final_fn(state), self.n)
        else:
            state = self._repartitioner(key_idx, fused=False)(state)
            final_fn = self._smap(
                lambda b: grouped_aggregate(b, key_idx, aggs, mode="final",
                                            key_bounds=kb,
                                            allow_dense=allow_dense), 1,
                stage="agg")
            out = final_fn(state)
        if node.default_gids and step in ("single", "final") \
                and out.host_count() == 0:
            from .local import _default_grouping_batch
            yield self._pad_shardable(_default_grouping_batch(node))
            return
        yield out

    @staticmethod
    def _wave_sig(b: Batch):
        """Trace signature a fused wave must hold constant: chunks are
        tree-stacked into ONE program, so capacity, schema and every
        column's dictionary object must match the wave's first chunk."""
        return (b.capacity, b.schema,
                tuple(id(c.dictionary) for c in b.columns))

    def _fused_agg_drain(self, src: Iterator[Batch], group: List[int],
                         aggs: List[AggSpec], kb):
        """Drain grouped-aggregation input through fused multi-round wave
        programs (tentpole tier A).

        Each wave stacks up to ``mesh_fused_loop_rounds`` chunks into ONE
        shard_map program whose body is a ``lax.fori_loop`` of
        partial-aggregate + state-merge at a STATIC state capacity proven
        from the dense key domain (dictionary vocab / bool / stats
        bounds — the PR 2/PR 10 machinery). The host dispatches once per
        wave instead of 3-4 times (+ a liveness sync) per chunk; the
        previous wave's carry is DONATED into the next wave's program so
        round-carried state stops churning buffers. Bounds violations
        fold into a replicated scalar that joins the query's single
        end-of-run error sync.

        Returns ``(state, leftover)``: the fused carry (None when the
        gate rejected the stream) and an iterator of chunks the caller's
        classic loop must still process."""
        from ..ops.aggregation import (dense_group_plan, has_drain_agg,
                                       _wide_state_aggs)
        first = next(src, None)
        if first is None:
            return None, iter(())
        if has_drain_agg(aggs) or _wide_state_aggs(aggs):
            # drain/wide states don't take the dense path in-program;
            # without it no static carry capacity can be proven
            return None, itertools.chain([first], src)
        kb_list = list(kb) if kb else None
        plan = dense_group_plan(first, group, _FUSED_STATE_SLOTS, kb_list)
        if plan is None:
            return None, itertools.chain([first], src)
        cap_out = bucket_capacity(plan.K + 1)
        key_idx = list(range(len(group)))
        sig0 = self._wave_sig(first)
        wave_fns: Dict[Tuple[int, bool], object] = {}

        def run_wave(carry: Optional[Batch],
                     chunks: List[Batch]) -> Batch:
            rounds = 1 << max(len(chunks) - 1, 0).bit_length()
            if rounds > len(chunks):
                # pad to a power of two so wave programs stay few: dead
                # copies of the last chunk (mask off -> overflow slot)
                dead = Batch(chunks[-1].schema, chunks[-1].columns,
                             jnp.zeros_like(chunks[-1].row_mask))
                chunks = chunks + [dead] * (rounds - len(chunks))
            has_carry = carry is not None
            fn = wave_fns.get((rounds, has_carry))
            if fn is None:
                fn = wave_fns[(rounds, has_carry)] = self._smap(
                    _fused_agg_wave_fn(group, key_idx, aggs, kb,
                                       cap_out, has_carry, self.axis),
                    rounds + (1 if has_carry else 0),
                    n_out=2, replicated_out=(1,), stage="agg",
                    donate=(0,) if has_carry else (),
                    rounds=rounds)
            out, viol = fn(*([carry] if has_carry else []), *chunks)
            if kb is not None:
                self.error_flags.append(viol)
            return out

        state: Optional[Batch] = None
        pending = [first]
        leftover: Optional[Batch] = None
        for chunk in src:
            if self._wave_sig(chunk) != sig0:
                # signature drifted (dictionary / capacity change): hand
                # the rest back to the classic per-chunk plane, which
                # merges into the fused carry via concat-remap
                leftover = chunk
                break
            pending.append(chunk)
            if len(pending) >= self.fused_loop_rounds:
                state = run_wave(state, pending)
                pending = []
        if pending:
            state = run_wave(state, pending)
        if leftover is not None:
            return state, itertools.chain([leftover], src)
        return state, iter(())

    def _global_agg(self, node: AggregationNode,
                    aggs: List[AggSpec]) -> Batch:
        step = node.step
        partial_fn = self._smap(
            lambda b: global_aggregate(b, aggs, mode="partial"), 1, stage="agg")
        merge_fn = self._smap(
            lambda a, b: global_aggregate(
                concat_batches([a, b]), aggs, mode="merge"), 2, stage="agg")
        state: Optional[Batch] = None
        for chunk in self.run(node.child):
            partial = (chunk if step == "final" else partial_fn(chunk))
            state = partial if state is None else merge_fn(state, partial)
        if state is None:
            empty = Batch.from_arrays(
                _plan_schema(node.child),
                [[] for _ in node.child.fields], num_rows=0)
            state = partial_fn(self._pad_shardable(empty))
        if step == "partial":
            return state          # shard-local states; FINAL gathers
        # gather every shard's state and finalize replicated
        final_fn = self._smap(
            lambda b: global_aggregate(
                _gathered(b, self.axis), aggs, mode="final"), 1, stage="agg")
        out = final_fn(state)
        # output is identical on every shard; mask all but shard 0
        return _keep_first_shard(out, self.n)

    # -- joins -----------------------------------------------------------------
    def _JoinNode(self, node: JoinNode) -> Iterator[Batch]:
        build = self._drain(node.right)
        if node.join_type == "cross":
            yield from self._cross_join(node, build)
            return
        residual = (self._resolve(node.residual)
                    if node.residual is not None else None)
        # plain (unchecked) filter: it runs INSIDE the shard_map'd probe
        # step, where a host-side error collector would leak tracers; a
        # residual row error here degrades to dropped-row semantics
        residual_fn = (compile_filter(residual, _plan_schema(node))
                       if residual is not None else None)
        residual_outer = (residual_fn is not None
                          and node.join_type in ("left", "full"))
        payload = list(range(len(node.right.fields)))
        payload_names = [f"$b{i}" for i in payload]
        out_schema = _plan_schema(node)

        if build is None:
            for probe in self.run(node.left):
                if node.join_type in ("left", "full"):
                    yield self._null_extend(probe, node)
            return

        lkeys, rkeys = list(node.left_keys), list(node.right_keys)
        replicated = node.distribution == "replicated"
        track_full = node.join_type == "full"
        pmap = repart_build = None
        if replicated:
            # FIXED_BROADCAST: build side replicated to every shard —
            # device-to-device all-gather, no host staging
            build_side = self._replicate_device(build)
        else:
            # FIXED_HASH: build repartitioned by join key over ICI once.
            # ONE _PartitionMap covers build AND probe exchanges, so
            # equal keys colocate under any (re-balanced) assignment.
            # FULL joins pin the map (adaptive=False): their per-shard
            # unmatched-build masks cannot survive rows moving shards.
            pmap = _PartitionMap(self.n, adaptive=not track_full)
            repart_build = self._repartitioner(rkeys, pmap)
            e0 = pmap.epoch
            build_side = repart_build(build)
            # fused plane: fold the build round's counts NOW (one sync,
            # before any probe ships) so a skewed build re-balances the
            # shared map before the probe stream commits to it. The
            # fused ship ran BEFORE its counts were seen, so a verdict
            # from its own round means the build itself sits under the
            # stale assignment — re-ship it once
            repart_build.observe_pending()
            if pmap.epoch != e0:
                build_side = repart_build.replay(build)

        # prepare the build ONCE per shard (the LookupSource role, same
        # contract as exec/local.py): every probe program takes the
        # prepared pytree instead of re-sorting the build per probe
        # batch. Planner key_bounds (stats-driven strategy selection)
        # build the mixed-radix direct-address table; the build is
        # cross-checked against the promised bounds through the
        # row-error channel before any probe runs.
        from ..ops.join import (direct_keyed_plan, prepare_build,
                                prepare_direct_keyed)
        from ..ops.jitcache import key_bounds_violation_jit
        from .local import _note_join_strategy, bool_property
        kb_plan = (direct_keyed_plan(tuple(node.key_bounds))
                   if node.key_bounds
                   and bool_property(self.session, "join_dense_path",
                                     True) else None)
        if kb_plan is not None:
            los, sizes, K = kb_plan
            cap = bucket_capacity(K)

            def prep_local(b: Batch):
                return prepare_direct_keyed(b, rkeys, los, sizes, cap)
            # GSPMD reduces the sharded violation scan to one scalar;
            # it joins the query's single end-of-run error sync
            self.error_flags.append(key_bounds_violation_jit(
                build, tuple(rkeys), tuple(node.key_bounds)))
        else:
            def prep_local(b: Batch):
                return prepare_build(b, rkeys)
        prep_in = (0,) if replicated else ()
        prep_smap = self._smap(prep_local, 1, replicated_in=prep_in,
                               replicated_out=replicated, stage="join")
        prepared = prep_smap(build_side)
        _note_join_strategy(
            self.stats, node,
            ("direct" if kb_plan is not None else "sorted")
            if node.build_unique else "expand", node.distribution)
        # probe programs: build + prepared ride the same sharding
        rep_in2 = (1, 2) if replicated else ()

        # FULL OUTER probes like LEFT; the unmatched-build tail is emitted
        # after the probe stream (per shard — the optimizer forces
        # partitioned distribution, so each build row lives on one shard)
        jt = "left" if node.join_type == "full" else node.join_type

        npro = len(node.left.fields)

        def local_probe(probe_l: Batch, build_l: Batch, prep_l,
                        maxk: int) -> Batch:
            if node.build_unique:
                out = lookup_join(probe_l, build_l, lkeys, rkeys,
                                  payload, payload_names, jt,
                                  prepared=prep_l)
            else:
                out = expand_join(probe_l, build_l, lkeys, rkeys,
                                  payload, payload_names, jt,
                                  max_matches=maxk, prepared=prep_l)
            out = Batch(out_schema, out.columns, out.row_mask)
            return residual_fn(out) if residual_fn else out

        def local_probe_outer(probe_l: Batch, build_l: Batch, prep_l,
                              maxk: int):
            """LEFT/FULL with a residual, shard-local (same contract as
            the local executor's _probe_outer_residual: residual gates
            matches, probe rows never drop; returns (batch,
            surviving-build-match mask) — the mask feeds the FULL
            unmatched-build tail)."""
            from ..ops.join import (expand_match_origins, semi_join_mask,
                                    unique_match_build_mask)
            if node.build_unique:
                out = lookup_join(probe_l, build_l, lkeys, rkeys,
                                  payload, payload_names, "left",
                                  prepared=prep_l)
                match = semi_join_mask(probe_l, build_l, lkeys, rkeys,
                                       prepared=prep_l)
                gated = residual_fn(Batch(out_schema, out.columns,
                                          probe_l.row_mask & match))
                survived = gated.row_mask
                cols = list(out.columns[:npro])
                for c in out.columns[npro:]:
                    cols.append(Column(c.type, c.data,
                                       c.validity & survived,
                                       c.dictionary))
                bmask = (unique_match_build_mask(
                    probe_l, build_l, lkeys, rkeys, survived,
                    prepared=prep_l)
                    if track_full
                    else jnp.zeros(build_l.capacity, dtype=bool))
                return Batch(out_schema, cols, probe_l.row_mask), bmask
            k = max(1, maxk)
            e = expand_join(probe_l, build_l, lkeys, rkeys, payload,
                            payload_names, "inner", max_matches=k,
                            prepared=prep_l)
            gated = residual_fn(Batch(out_schema, e.columns,
                                      e.row_mask))
            survived = gated.row_mask
            C = probe_l.capacity
            has = jnp.any(survived.reshape(k, C), axis=0)
            # reinstate unmatched probe rows in their slot-0 lanes with
            # null payload (lane = slot*C + i, so slot 0 is the first C)
            reinstate = jnp.zeros(k * C, dtype=bool).at[:C].set(
                probe_l.row_mask & ~has)
            cols = []
            for i, c in enumerate(e.columns):
                if i < npro:
                    cols.append(c)
                else:
                    cols.append(Column(c.type, c.data,
                                       c.validity & survived,
                                       c.dictionary))
            if track_full:
                orig, _ = expand_match_origins(probe_l, build_l, lkeys,
                                               rkeys, k,
                                               prepared=prep_l)
                n = build_l.capacity
                bmask = jnp.zeros(n, dtype=bool).at[
                    jnp.where(survived, orig, n)].max(survived,
                                                      mode="drop")
            else:
                bmask = jnp.zeros(build_l.capacity, dtype=bool)
            return Batch(out_schema, cols,
                         survived | reinstate), bmask

        count_fn = None
        maxk_static: Optional[int] = None
        if not node.build_unique:
            # ONE build-side multiplicity readback bounds every probe
            # batch's match count (mirrors exec/local.py): the per-probe-
            # batch count sync only returns for skewed builds, where the
            # bound would oversize every batch's expansion
            from ..ops.join import max_multiplicity
            mult_fn = self._smap(
                lambda pr: max_multiplicity(pr)[None].astype(jnp.int64),
                1, replicated_in=(0,) if replicated else (),
                flight_kind=None, stage="join")
            _drain_inputs(prepared)
            bound = int(np.asarray(_sync_record(
                "join-multiplicity", mult_fn, prepared)).max())
            if bound <= self.SKEW_MATCH_LIMIT:
                # the bound survives re-assignment: a key's rows move
                # between shards ATOMICALLY (bucket granularity), so a
                # shard's max per-key multiplicity never exceeds the
                # global max this readback saw
                maxk_static = bucket_capacity(max(bound, 1), minimum=1)
            else:
                def local_count(p: Batch, b: Batch, pr) -> jnp.ndarray:
                    return match_count_max(p, b, lkeys, rkeys,
                                           prepared=pr)[None]
                count_fn = self._smap(local_count, 3,
                                      replicated_in=rep_in2,
                                      flight_kind=None, stage="join")

        repart_probe = (None if replicated
                        else self._repartitioner(lkeys, pmap))
        join_fns: Dict[int, object] = {}
        match_fn = (self._smap(
            lambda p, b, pr: build_match_mask(p, b, lkeys, rkeys,
                                              prepared=pr), 3,
            replicated_in=rep_in2, stage="join")
            if track_full else None)
        build_matched = None
        built_epoch = pmap.epoch if pmap is not None else 0
        # fused probe plane (tentpole tier B): when the match bound is
        # static (no per-batch count sync) and no outer/residual bookkeeping
        # rides along, the key exchange FUSES into the probe program —
        # repartition collectives and probe compute are one dispatch, with
        # the round's bucket counts as a device-resident second output that
        # the deferred skew check folds in without blocking the stream
        fuse_probe = (repart_probe is not None and repart_probe.fused
                      and count_fn is None and not residual_outer
                      and not track_full)
        fused_probe_fns: Dict[Tuple, object] = {}
        for probe in self.run(node.left):
            if fuse_probe:
                if pmap.epoch != built_epoch:
                    # deferred skew verdict landed: loop-exit-and-rebuild —
                    # re-ship the retained build under the new assignment
                    # before the next fused round commits to it
                    build_side = repart_build.replay(build)
                    prepared = prep_smap(build_side)
                    built_epoch = pmap.epoch
                from .failpoints import FAILPOINTS
                fl = _flight.current_flight()
                t0 = time.perf_counter()
                FAILPOINTS.hit("mesh.repartition")
                maxk = maxk_static if maxk_static is not None else 1
                key = (pmap.assign, repart_probe.fused_quota(probe), maxk)
                fn = fused_probe_fns.get(key)
                if fn is None:
                    from ..parallel.exchange import repartition_fused
                    _a, _q, _k = key
                    _ax, _n = self.axis, self.n

                    def fused_probe(p, b, pr, _a=_a, _q=_q, _k=_k):
                        shipped, counts = repartition_fused(
                            p, lkeys, _ax, _n, _a, _q)
                        return local_probe(shipped, b, pr, _k), counts
                    fn = fused_probe_fns[key] = self._smap(
                        fused_probe, 3, replicated_in=rep_in2, n_out=2,
                        flight_kind=None, stage="join")
                out, counts = fn(probe, build_side, prepared)
                repart_probe.note_counts(counts)
                if fl is not None:
                    # exchange + probe are ONE program now: the round
                    # record is a repartition record whose wall covers
                    # the whole fused dispatch
                    fl.record("repartition",
                              wall=time.perf_counter() - t0)
                yield out
                continue
            if repart_probe is not None:
                probe = repart_probe(probe)
                if pmap.epoch != built_epoch:
                    # adaptive re-split (StageMonitor's skew verdict in
                    # action): a hot bucket moved shards, so the
                    # prepared build is stale — re-ship the retained
                    # build under the new assignment and re-prepare,
                    # once per epoch, before the next probe batch
                    build_side = repart_build.replay(build)
                    prepared = prep_smap(build_side)
                    built_epoch = pmap.epoch
            maxk = 1
            if maxk_static is not None:
                maxk = maxk_static
            elif count_fn is not None:
                _drain_inputs(probe, build_side, prepared)
                maxk = bucket_capacity(
                    max(int(np.asarray(_sync_record(
                        "join-match-count", count_fn, probe,
                        build_side, prepared)).max()), 1),
                    minimum=1)
            fn = join_fns.get(maxk)
            if fn is None:
                if residual_outer:
                    fn = join_fns[maxk] = self._smap(
                        lambda p, b, pr, _k=maxk: local_probe_outer(
                            p, b, pr, _k),
                        3, replicated_in=rep_in2, stage="join")
                else:
                    fn = join_fns[maxk] = self._smap(
                        lambda p, b, pr, _k=maxk: local_probe(
                            p, b, pr, _k), 3,
                        replicated_in=rep_in2, stage="join")
            if residual_outer:
                out, m = fn(probe, build_side, prepared)
                if track_full:
                    build_matched = (m if build_matched is None
                                     else build_matched | m)
                yield out
                continue
            if track_full:
                m = match_fn(probe, build_side, prepared)
                build_matched = (m if build_matched is None
                                 else build_matched | m)
            yield fn(probe, build_side, prepared)
        if repart_probe is not None:
            # per-stage control-scalar fetch: any still-pending fused
            # round counts fold into the shared map exactly once here,
            # so skew stats never silently drop at stage end
            repart_probe.observe_pending()
        if track_full:
            left_fields = node.left.fields

            def local_tail(b_l: Batch, matched_l) -> Batch:
                mask = b_l.row_mask & ~matched_l
                novalid = jnp.zeros(b_l.capacity, dtype=bool)
                cols = [Column(f.type,
                               jnp.zeros(b_l.capacity,
                                         dtype=f.type.storage_dtype),
                               novalid, () if f.type.is_string else None)
                        for f in left_fields]
                cols.extend(b_l.columns)
                return Batch(out_schema, cols, mask)

            if build_matched is None:
                build_matched = jnp.zeros_like(build_side.row_mask)
            yield self._smap(local_tail, 2, stage="join")(build_side, build_matched)

    def _SemiJoinNode(self, node: SemiJoinNode) -> Iterator[Batch]:
        build = self._drain(node.filtering)
        skeys, fkeys = list(node.source_keys), list(node.filtering_keys)
        neg = node.negated
        if build is None:
            for b in self.run(node.source):
                if neg:
                    yield b
            return
        # stats-driven distribution (optimizer._attach_join_strategy):
        # a large filtering set hash-partitions BOTH sides by key so
        # membership never broadcasts — matching keys colocate, so
        # per-shard verdicts compose exactly. NULL-aware anti joins
        # always replicate (their build_has_null/build_empty facts are
        # global) — the optimizer never marks them partitioned.
        # (mark-joins — residual semis — keep the replicated path: their
        # expansion probes are already bounded per shard)
        partitioned = (node.distribution == "partitioned"
                       and not (neg and node.null_aware)
                       and node.residual is None)
        from .local import _note_join_strategy
        pmap = repart_build = None
        if partitioned:
            # one map for both sides (see _JoinNode): verdicts compose
            # per shard under any re-balanced assignment
            pmap = _PartitionMap(self.n)
            repart_build = self._repartitioner(fkeys, pmap)
            e0 = pmap.epoch
            build_rep = repart_build(build)
            # fold the build round's counts before the source stream
            # commits to the shared assignment; re-ship once if the
            # build's own round triggered the re-split (see _JoinNode)
            repart_build.observe_pending()
            if pmap.epoch != e0:
                build_rep = repart_build.replay(build)
            repart_src = self._repartitioner(skeys, pmap)
        else:
            build_rep = self._replicate_device(build)
            repart_src = None
        # record the EXECUTED distribution: a residual mark-join the
        # planner marked partitioned still runs replicated here
        _note_join_strategy(self.stats, node, "sorted",
                            "partitioned" if partitioned
                            else "replicated")

        if node.residual is None:
            # prepare the membership table ONCE per shard (instead of
            # re-sorting the filtering side inside every probe program)
            from ..ops.join import prepare_build
            prep_smap = self._smap(lambda f: prepare_build(f, fkeys), 1,
                                   replicated_in=(0,) if not partitioned
                                   else (),
                                   replicated_out=not partitioned, stage="semi")
            prep = prep_smap(build_rep)

            def local(b: Batch, flt: Batch, pr) -> Batch:
                mask = semi_join_mask(b, flt, skeys, fkeys, negated=neg,
                                      null_aware=node.null_aware,
                                      prepared=pr)
                return Batch(b.schema, b.columns, mask)

            fn = self._smap(local, 3,
                            replicated_in=(1, 2) if not partitioned
                            else (), stage="semi")
            built_epoch = pmap.epoch if pmap is not None else 0
            # fused source plane: key exchange + membership probe as ONE
            # dispatch per round, bucket counts deferred (see _JoinNode)
            fuse_src = repart_src is not None and repart_src.fused
            fused_fns: Dict[Tuple, object] = {}
            for b in self.run(node.source):
                if fuse_src:
                    if pmap.epoch != built_epoch:
                        build_rep = repart_build.replay(build)
                        prep = prep_smap(build_rep)
                        built_epoch = pmap.epoch
                    from .failpoints import FAILPOINTS
                    fl = _flight.current_flight()
                    t0 = time.perf_counter()
                    FAILPOINTS.hit("mesh.repartition")
                    key = (pmap.assign, repart_src.fused_quota(b))
                    f2 = fused_fns.get(key)
                    if f2 is None:
                        from ..parallel.exchange import repartition_fused
                        _a, _q = key
                        _ax, _n = self.axis, self.n

                        def fused_semi(p, flt, pr, _a=_a, _q=_q):
                            shipped, counts = repartition_fused(
                                p, skeys, _ax, _n, _a, _q)
                            return local(shipped, flt, pr), counts
                        f2 = fused_fns[key] = self._smap(
                            fused_semi, 3, n_out=2, flight_kind=None,
                            stage="semi")
                    out, counts = f2(b, build_rep, prep)
                    repart_src.note_counts(counts)
                    if fl is not None:
                        fl.record("repartition",
                                  wall=time.perf_counter() - t0)
                    yield out
                    continue
                if repart_src is not None:
                    b = repart_src(b)
                    if pmap.epoch != built_epoch:
                        # adaptive re-split: re-ship + re-prepare the
                        # filtering side under the new assignment
                        build_rep = repart_build.replay(build)
                        prep = prep_smap(build_rep)
                        built_epoch = pmap.epoch
                yield fn(b, build_rep, prep)
            if repart_src is not None:
                repart_src.observe_pending()
            return

        # mark-join (EXISTS with residual): shard-local against the
        # replicated filtering side; expansion factor from ONE build-side
        # multiplicity readback (skewed builds per-chunk, as in the join)
        from .local import mark_exists_mask
        from ..expr.params import collect_params, current_args
        from ..ops.join import build_sorted, max_multiplicity
        mult_fn = self._smap(
            lambda f: max_multiplicity(
                build_sorted(f, fkeys))[None].astype(jnp.int64), 1,
            replicated_in=(0,), flight_kind=None, stage="semi")
        _drain_inputs(build_rep)
        bound = int(np.asarray(_sync_record(
            "semi-multiplicity", mult_fn, build_rep)).max())
        res_maxk = (bucket_capacity(max(bound, 1), minimum=1)
                    if bound <= self.SKEW_MATCH_LIMIT else None)
        count_fn = (None if res_maxk is not None else self._smap(
            lambda p, f: match_count_max(p, f, skeys, fkeys)[None], 2,
            replicated_in=(1,), flight_kind=None, stage="semi"))
        fns: Dict[int, object] = {}
        for b in self.run(node.source):
            if res_maxk is not None:
                maxk = res_maxk
            else:
                _drain_inputs(b, build_rep)
                maxk = bucket_capacity(
                    max(int(np.asarray(_sync_record(
                        "semi-match-count", count_fn, b,
                        build_rep)).max()), 1),
                    minimum=1)
            fn = fns.get(maxk)
            if fn is None:
                def local_mark(p: Batch, f: Batch, _k=maxk) -> Batch:
                    # a plan template's parameters as the values bound
                    # now: constants of this shard program
                    slots = collect_params([node.residual])
                    mask, _ = mark_exists_mask(
                        p, f, skeys, fkeys, node.residual, neg, _k,
                        pargs=current_args(slots) if slots else ())
                    return Batch(p.schema, p.columns, mask)
                fn = fns[maxk] = self._smap(local_mark, 2,
                                            replicated_in=(1,), stage="semi")
            yield fn(b, build_rep)

    # -- sort family: local pre-reduce + gather-merge -------------------------
    @staticmethod
    def _sort_sentinel_dt(dtype):
        if dtype == jnp.uint64:
            return jnp.iinfo(jnp.uint64).max
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.inf
        return jnp.iinfo(dtype).max

    def _SortNode(self, node: SortNode) -> Iterator[Batch]:
        b = self._drain(node.child)
        if b is None:
            return
        keys = [SortKey(k.index, k.ascending, k.nulls_first)
                for k in node.keys]
        n = self.n
        samples_per_shard = 64
        # bind value-stable locals (not self) so the program fingerprints
        # for the cross-query cache; _sort_sentinel_dt is a staticmethod,
        # so the attribute access yields a plain function
        _ax = self.axis
        _sentinel_dt = self._sort_sentinel_dt

        # RANGE-partitioned distributed sort (reference dist-sort.rst +
        # MergeOperator.java:45, reshaped for SPMD): sample the primary
        # key per shard, agree on splitters via all_gather, all-to-all
        # rows into disjoint key ranges, sort shard-locally — shard-major
        # concatenation IS the global order; no host re-sort, no N-way
        # merge stream.
        def program(x: Batch) -> Batch:
            from ..ops.sort import _sortable
            from ..parallel.exchange import repartition_by_ids
            x = sort_batch(x, keys)          # local sort (dead rows last)
            k0 = keys[0]
            null_rank, data = _sortable(x.columns[k0.column], k0)
            nulls_first = k0.effective_nulls_first()
            live = x.row_mask
            nn = live & (null_rank == (1 if nulls_first else 0))
            # after the local sort, non-null live rows are contiguous
            n_nn = jnp.sum(nn.astype(jnp.int32))
            start = jnp.sum((live & ~nn).astype(jnp.int32)) \
                if nulls_first else jnp.int32(0)
            m = samples_per_shard
            step = jnp.maximum(n_nn, 1).astype(jnp.float32) / m
            pos = (start + ((jnp.arange(m, dtype=jnp.float32) + 0.5)
                            * step).astype(jnp.int32))
            pos = jnp.clip(pos, 0, x.capacity - 1)
            local_samples = jnp.take(data, pos, axis=0)
            # shards with no non-null rows contribute max-sentinels so
            # they never pull the splitters down
            sent = jnp.full((m,), _sentinel_dt(data.dtype),
                            dtype=data.dtype)
            local_samples = jnp.where(n_nn > 0, local_samples, sent)
            all_samples = jax.lax.all_gather(
                local_samples, _ax, tiled=True)       # [n*m]
            s_sorted = jax.lax.sort([all_samples])[0]
            splitters = jnp.take(
                s_sorted, jnp.arange(1, n, dtype=jnp.int32) * m, axis=0)
            pid = jnp.searchsorted(splitters, data,
                                   side="right").astype(jnp.int32)
            null_pid = jnp.int32(0 if nulls_first else n - 1)
            pid = jnp.where(nn, pid, null_pid)
            ex = repartition_by_ids(Batch(x.schema, x.columns, live),
                                    pid, _ax, n)
            return sort_batch(ex, keys)

        # shard-major concatenation of the range-partitioned shards IS the
        # global order — yield the device-resident sharded batch directly
        yield self._smap(program, 1, stage="sort")(b)

    def _TopNNode(self, node: TopNNode) -> Iterator[Batch]:
        """Shard-local top-n accumulation (collective-free per batch),
        then ONE device-side all-gather merge at the end — replaces the
        round-4 path that gathered every candidate batch to the host
        (reference TopNOperator keeps a per-driver heap the same way and
        merges once at output)."""
        keys = [SortKey(k.index, k.ascending, k.nulls_first)
                for k in node.keys]
        cap = bucket_capacity(node.count)
        local_topn = self._smap(
            lambda b: top_n(b, keys, node.count).compact(cap, check=False), 1, stage="sort")
        merge_fn = self._smap(
            lambda s, c: top_n(concat_batches([s, c]), keys,
                               node.count).compact(cap, check=False), 2, stage="sort")
        state: Optional[Batch] = None
        for b in self.run(node.child):
            cand = local_topn(b)
            state = cand if state is None else merge_fn(state, cand)
        if state is not None:
            # every shard computes the same global top-n over the gathered
            # candidates; mask all but shard 0's copy
            final_fn = self._smap(
                lambda s, _ax=self.axis: sort_batch(
                    top_n(_gathered(s, _ax), keys, node.count),
                    keys), 1, stage="sort")
            yield _keep_first_shard(final_fn(state), self.n)

    def _UnnestNode(self, node) -> Iterator[Batch]:
        # shard-local expansion: every shard expands by the same static
        # element count L, so per-shard capacity stays uniform (cap_l*L)
        # and downstream exchanges keep mesh divisibility
        from .local import unnest_expand_fn, _plan_schema as _ps
        exprs = tuple(self._resolve(e) for e in node.exprs)
        fn = unnest_expand_fn(exprs, node.ordinality, _ps(node))

        def local_unnest(x: Batch):
            out, err = fn(x)
            e = (jnp.zeros((1,), jnp.int32) if err is None
                 else err.reshape(1).astype(jnp.int32))
            return out, e

        sfn = self._smap(local_unnest, 1, n_out=2)
        for b in self.run(node.child):
            out, err = sfn(b)
            self.error_flags.append(jnp.max(err))
            yield out

    def _WindowNode(self, node) -> Iterator[Batch]:
        from ..ops.window import WindowSpec, evaluate_window
        b = self._drain(node.child)
        if b is None:
            return
        specs = [WindowSpec(f.fn, f.args, f.output_type, f.name, f.offset,
                            f.ignore_order, f.frame, f.frame_start,
                            f.frame_end) for f in node.functions]
        keys = [SortKey(k.index, k.ascending, k.nulls_first)
                for k in node.order_keys]
        parts = list(node.partition_indices)
        schema = _plan_schema(node)
        if parts:
            # colocate partitions via hash exchange, evaluate shard-locally
            b = self._repartitioner(parts, fused=False)(b)
            fn = self._smap(
                lambda x: evaluate_window(x, parts, keys, specs), 1)
            out = fn(b)
        else:
            # single global partition: every shard evaluates the window
            # over the device-gathered batch (replicated compute over ICI;
            # no host round trip); keep shard 0's copy
            fn = self._smap(
                lambda x: evaluate_window(_gathered(x, self.axis),
                                          parts, keys, specs), 1)
            out = _keep_first_shard(fn(b), self.n)
        yield Batch(schema, out.columns, out.row_mask)

    def _DistinctNode(self, node: DistinctNode) -> Iterator[Batch]:
        b = self._drain(node.child)
        if b is None:
            return
        cols = list(range(len(node.fields)))
        allow_dense = bool_property(self.session, "dense_grouping", True)
        kb = tuple(node.key_bounds) if node.key_bounds else None
        if kb is not None and allow_dense:
            # unconditional hard-invariant check — see _AggregationNode
            from ..ops.jitcache import key_bounds_violation_jit
            self.error_flags.append(key_bounds_violation_jit(b, cols, kb))
        b = self._repartitioner(cols, fused=False)(b)
        fn = self._smap(
            lambda x: grouped_aggregate(x, cols, [], mode="single",
                                        key_bounds=kb,
                                        allow_dense=allow_dense), 1)
        yield fn(b)

    def _MarkDistinctNode(self, node) -> Iterator[Batch]:
        """Colocate rows by the distinct tuple, then flag shard-locally:
        equal tuples land on one shard, so first-occurrence is global."""
        import jax.numpy as jnp
        from ..ops.aggregation import mark_distinct_flags
        from .local import _plan_schema as plan_schema
        b = self._drain(node.child)
        if b is None:
            return
        b = self._repartitioner(list(node.cols), fused=False)(b)
        schema = plan_schema(node)

        def local_mark(x: Batch) -> Batch:
            flags = mark_distinct_flags(x, list(node.cols))
            from ..batch import Column
            from .. import types as T
            col = Column(T.BOOLEAN, flags, x.row_mask, None)
            return Batch(schema, list(x.columns) + [col], x.row_mask)
        yield self._smap(local_mark, 1)(b)

    def _drain(self, node: PlanNode) -> Optional[Batch]:
        batches = list(self.run(node))
        if not batches:
            return None
        if len(batches) == 1:
            return batches[0]
        # concat shard-locally to keep the result sharded
        fn = self._smap(lambda *bs: concat_batches(list(bs)), len(batches), stage="scan")
        return fn(*batches)


# -- helpers -----------------------------------------------------------------

def _fused_agg_wave_fn(group, key_idx, aggs, kb, cap_out: int,
                       has_carry: bool, axis: str):
    """One-dispatch multi-round aggregation program (DrJAX pattern:
    MapReduce rounds as traced code, not host loops). Stacks the wave's
    chunks leaf-wise, then runs a ``lax.fori_loop`` of partial-aggregate
    + state-merge whose carry rides at the STATIC ``cap_out`` capacity
    the dense key domain proves. Returns ``(state, violation)`` where
    the violation scalar is pmax-replicated so it can join the query's
    single end-of-run error sync."""
    kb_t = tuple(kb) if kb else None
    group_t = tuple(group)

    def _partial(chunk: Batch) -> Batch:
        return grouped_aggregate(chunk, group, aggs, mode="partial",
                                 output_capacity=cap_out,
                                 key_bounds=kb_t, allow_dense=True)

    def fused_agg_wave(*args):
        chunks = args[1:] if has_carry else args
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *chunks)
        if kb_t is not None:
            # bounds check over every round at once: the stacked [R, C]
            # leaves broadcast straight through the violation predicate
            from ..ops.jitcache import _bounds_violation
            viol = jax.lax.pmax(
                _bounds_violation(group_t, kb_t)(stacked), axis)
        else:
            viol = jnp.int32(0)
        if has_carry:
            st0, lo = args[0], 0
        else:
            st0, lo = _partial(chunks[0]), 1

        def body(r, st):
            chunk = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, r, 0, keepdims=False), stacked)
            return grouped_aggregate(
                concat_batches([st, _partial(chunk)]), key_idx, aggs,
                mode="merge", output_capacity=cap_out, key_bounds=kb_t,
                allow_dense=True)

        return jax.lax.fori_loop(lo, len(chunks), body, st0), viol

    return fused_agg_wave


def _gathered(b: Batch, axis: str) -> Batch:
    from ..parallel.exchange import broadcast_batch
    return broadcast_batch(b, axis)


def _keep_first_shard(b: Batch, n: int) -> Batch:
    cap = b.capacity
    per = cap // n
    keep = jnp.arange(cap) < per
    return Batch(b.schema, b.columns, b.row_mask & keep)


def _host_col(typ, vocab):
    return Column(typ, jnp.zeros(1, dtype=jnp.int32),
                  jnp.zeros(1, dtype=bool), vocab)


def _apply_remap(codes: np.ndarray, remap: np.ndarray) -> np.ndarray:
    idx = np.where(codes >= 0, codes, len(remap) - 1)
    return remap[idx]
