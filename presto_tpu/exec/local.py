"""Local plan executor: logical plan -> streaming batch iterators.

Conceptual parity with the reference's LocalExecutionPlanner + Driver
pipelines (reference presto-main/.../sql/planner/LocalExecutionPlanner.java:357
visitTableScan/visitAggregation/visitJoin and operator/Driver.java): each
plan node becomes a generator over device batches, so scan->filter->project
->partial-agg chains stream without materializing, join build sides and
sorts drain their input exactly like HashBuilderOperator / OrderByOperator,
and expression compilation happens once per (expr, schema) via the kernel
compiler's cache.

Init plans (uncorrelated scalar subqueries) run before the main plan and
their scalar results substitute into expressions — the reference's
ExchangeClient-fed init semantics without a network hop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..batch import Batch, Column, Schema, bucket_capacity, concat_batches
from ..expr import ir
from ..expr.compiler import compile_filter, compile_projection
from ..expr.rewrite import rewrite as ir_rewrite
from ..ops.aggregation import AggSpec
from ..ops.jitcache import global_aggregate_jit as global_aggregate, grouped_aggregate_jit as grouped_aggregate, compact_jit
from ..ops.jitcache import (
    build_key_ranks_jit, build_match_mask_jit, expand_join_jit,
    key_bounds_violation_jit, lookup_join_jit, lookup_join_pallas_jit,
    match_count_max_jit, pack_sorted_payload_jit, prepare_build_jit,
    prepare_direct_jit, prepare_direct_keyed_jit, semi_join_mask_jit,
)
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER, device_sync

#: grouped-aggregation kernel dispatch, per operator (first batch decides
#: and the plan is shape-stable): dense composite-code path (broadcast or
#: scatter — no sort) vs the sort-segment path. The trace-level signal
#: the stats-bounded grouping tests assert on.
_AGG_DENSE_SELECTED = REGISTRY.counter("agg_dense_path_selected_total")
_AGG_SORT_SELECTED = REGISTRY.counter("agg_sort_path_selected_total")

#: the aggregation sink (exec/fused.py agg_step), per group-by operator
#: that computes partials: selected when its first batch folds through
#: the one-program-a-batch step, declined when it takes the per-operator
#: path; batches folded; states flushed into the merge buffer because a
#: batch's dictionaries gave the state another layout
_AGG_STEP_SELECTED = REGISTRY.counter("agg_step_selected_total")
_AGG_STEP_DECLINED = REGISTRY.counter("agg_step_declined_total")
_AGG_STEP_BATCHES = REGISTRY.counter("agg_step_batches_total")
_AGG_STEP_FLUSHES = REGISTRY.counter("agg_step_flushes_total")

#: fused-chain lane accounting: capacities entering the chain (source)
#: vs entering the tail's payload gathers (post mask + compaction). The
#: ratio IS the gather-lane reduction the selectivity-first head buys —
#: the observable the q27-shaped star-chain tests assert on.
_FUSED_SOURCE_LANES = REGISTRY.counter("fused_source_lanes_total")
_FUSED_TAIL_LANES = REGISTRY.counter("fused_tail_lanes_total")


#: residual semi/anti joins executed, by the form that decided the
#: residual, and the lanes ``expand_join`` produced for the m:n form
#: (none for the keyed one)
_SEMI_RESIDUAL = {form: REGISTRY.counter(f"semi_join_residual_total.{form}")
                  for form in ("keyed", "expand")}
_SEMI_EXPANDED_LANES = REGISTRY.counter("semi_join_expanded_lanes_total")

#: how each payload-reading probe launch read its build's payload
#: (``ops/join.payload_form`` over the capacities the program is traced
#: with): `composed` through the permutation at the probe's lanes,
#: `permuted` through sorted copies made at the build's
_JOIN_PAYLOAD = {form: REGISTRY.counter(f"join_payload_selected_total.{form}")
                 for form in ("composed", "permuted")}


def _note_join_strategy(stats, node, strategy: str, dist: str,
                        residual: Optional[str] = None) -> None:
    """Join-dispatch observability: one count per executed join/semi
    operator, labeled strategy (direct / compare / sorted / expand) x
    distribution — the trace-level signal the strategy-selection tests
    assert on, next to EXPLAIN ANALYZE's per-row [strategy ...] suffix.
    ``residual``: how a semi join's residual is decided (`keyed` on the
    one row of a unique build, `expand` over the m:n matches), counted
    in ``semi_join_residual_total.<form>`` and printed in the suffix."""
    REGISTRY.counter(
        f"join_strategy_selected_total.{strategy}.{dist}").inc()
    if residual is not None:
        _SEMI_RESIDUAL[residual].inc()
    if stats is not None and hasattr(stats, "record_join_strategy"):
        stats.record_join_strategy(node, strategy, dist, residual)


def _note_payload_form(probe_lanes: int, build_lanes: int, n_payload: int,
                       pallas: bool = False) -> None:
    """One count a probe launch that reads a build's payload.
    ``probe_lanes``: the positions the program reads (an expansion's
    k x capacity). The Pallas probe's kernel gathers from sorted planes
    in VMEM: `permuted` whatever the sizes."""
    _JOIN_PAYLOAD["permuted" if pallas else payload_form(
        probe_lanes, build_lanes, n_payload)].inc()


from ..ops.join import expand_join, payload_form
from ..ops.sort import SortKey, limit as limit_kernel, sort_batch, top_n
from ..planner.plan import (
    AggregationNode, DistinctNode, FilterNode, GroupIdNode, JoinNode,
    LimitNode, OutputNode, PlanNode, ProjectNode, SemiJoinNode, SortNode,
    TableScanNode, TopNNode, UnionNode, ValuesNode,
)
from ..planner.planner import InitPlanRef, LogicalPlan, Session
from ..planner.fold import fold_expr

from ..planner.planner import bool_property  # noqa: F401 (re-export)


@dataclasses.dataclass
class QueryResult:
    names: List[str]
    types: List[T.Type]
    rows: List[tuple]


def _default_grouping_batch(node: AggregationNode) -> Batch:
    """One default row per empty grouping set for empty-input
    aggregations (reference AggregationNode.hasDefaultOutput +
    AggregationOperator's default output page): keys NULL, $group_id set,
    count-family aggregates 0, everything else NULL."""
    nk = len(node.group_indices)
    data: Dict[str, tuple] = {}
    n = len(node.default_gids)
    for pos, f in enumerate(node.fields):
        if pos < nk - 1:
            vals = [None] * n
        elif pos == nk - 1:                 # the $group_id column
            vals = [int(g) for g in node.default_gids]
        else:
            agg = node.aggs[pos - nk]
            zero = agg.fn in ("count", "count_star", "approx_distinct")
            vals = [0 if zero else None] * n
        data[f.name] = (f.type, vals)
    return Batch.from_pydict(data)


def run_init_plans(ex, plan: LogicalPlan) -> None:
    """Run uncorrelated scalar subqueries (init plans), exposing results to
    the main plan AND to later init plans: inner subqueries are appended
    first (lower indices), so binding the live list to the executor before
    the loop makes a nested init plan's InitPlanRef resolvable while the
    outer one runs."""
    ex.mark_shared(list(plan.init_plans) + [plan.root])
    ex.init_values = init_values = []
    for p in plan.init_plans:
        rows = [r for b in ex.run(p) for r in b.to_pylist()]
        if len(rows) > 1:
            raise ValueError("scalar subquery returned more than one row")
        init_values.append(rows[0][0] if rows else None)


def execute_plan(plan: LogicalPlan, session: Session,
                 rows_per_batch: int = 1 << 17, stats=None,
                 collect_rows: bool = True, cancel_event=None,
                 split_restrict=None) -> QueryResult:
    import time as _time

    from ..expr import params as P
    from ..obs import flight as _flight
    from ..obs.profiler import profiled
    from ..obs.trace import current_span_ids
    from .taskexec import GLOBAL as scheduler
    # mesh-native execution (the default with >1 device): the SPMD
    # executor shards this plan over the device mesh whenever the
    # auto-router (exec/distributed.select_mesh) accepts it —
    # mesh_execution=off pins the single-device path. Split-restricted
    # runs (result-cache incremental delta) stay single-device: the
    # restriction applies at the local scan node.
    from .distributed import (
        DistributedExecutor, mesh_flight_on, select_mesh,
    )
    bindings = getattr(session, "param_bindings", None)
    mesh = select_mesh(session, plan) if split_restrict is None else None
    if mesh is not None and bindings:
        # SPMD shard programs trace expressions inside their own jits
        # where a Param has no operand channel — materialize this
        # query's bindings into literals (correctness over executable
        # sharing; the cached template itself is never mutated)
        plan = P.bind_plan(plan, bindings)
        bindings = None
    if mesh is not None:
        ex = DistributedExecutor(session, rows_per_batch, mesh,
                                 stats=stats)
        n_chips = int(mesh.devices.size)
    else:
        ex = _Executor(session, rows_per_batch, stats=stats)
        n_chips = 1
    ex.cancel_event = cancel_event
    ex.split_restrict = split_restrict
    # admitted queries register under their resource group's scheduler
    # share (serving/groups.py): quanta are allotted per group by
    # schedulingWeight, then per task within the group — and billed
    # per chip, so a mesh query pays for every device it occupies
    serving = getattr(session, "serving", None)
    handle = (scheduler.task(
        name=str(id(ex)),
        group=serving.scheduler_group if serving is not None else "",
        weight=serving.weight if serving is not None else 1,
        label=serving.group_path if serving is not None else None,
        devices=n_chips)
        if bool_property(session, "fair_scheduling", True) else None)
    # device-time profiling: per-dispatch block_until_ready bracketing +
    # per-operator attribution (obs/profiler.py). On under the `profile`
    # session property, and always under EXPLAIN ANALYZE — analyze mode
    # already pays a per-batch sync for live row counts, so device truth
    # rides along; plain queries pay one contextvar load per dispatch.
    profile_on = (bool_property(session, "profile", False)
                  or (stats is not None
                      and getattr(stats, "count_rows", False)))
    # mesh flight recorder (obs/flight.py): every mesh-path execution
    # records its exchange rounds for the post-query wall-clock
    # attribution, unless mesh_flight=off
    flight = None
    fl_token = None
    if mesh is not None and mesh_flight_on(session):
        qid = (str(current_span_ids().get("query_id") or "")
               or f"mesh_{_flight.next_seq():06d}")
        flight = _flight.FlightRecorder(qid, int(mesh.devices.size))
        fl_token = _flight.CURRENT_FLIGHT.set(flight)
    t_flight0 = _time.perf_counter()
    try:
        # template bindings: ir.Param kernels fetch this query's
        # literal values from the scope (exchange driver threads copy
        # their spawn context, so the scope survives the q3-style
        # background pipelines)
        with P.bound(bindings), profiled(profile_on):
            run_init_plans(ex, plan)
            root = plan.root
            rows: List[tuple] = []
            out_batches: List[Batch] = []
            # one fair-scheduler quantum per produced output batch:
            # concurrent queries interleave at batch granularity by
            # cumulative device time (the reference's TaskExecutor
            # 1s-quantum role)
            it = ex.run(root.child)
            sentinel = object()
            try:
                while True:
                    # cancellation interrupts between quanta, like the
                    # reference Driver checking its DriverYieldSignal/state
                    # between page moves (operator/Driver.java:262;
                    # DispatchManager.java:134)
                    ex._check_cancel()
                    b = scheduler.run_quantum(handle,
                                              lambda: next(it, sentinel))
                    if b is sentinel:
                        break
                    if collect_rows:
                        out_batches.append(b)
            finally:
                # closing the generator runs suspended finally blocks (the
                # threaded scan's stop.set()) so cancel/error doesn't leave
                # prefetch workers spinning
                it.close()
            ex.check_errors()
            if collect_rows:
                if flight is not None:
                    with flight.timed("drain"):
                        rows = [r for b in out_batches
                                for r in b.to_pylist()]
                else:
                    rows = [r for b in out_batches
                            for r in b.to_pylist()]
            return QueryResult(names=[f.name for f in root.fields],
                               types=[f.type for f in root.fields],
                               rows=rows)
    finally:
        if flight is not None:
            _flight.CURRENT_FLIGHT.reset(fl_token)
            flight.finish(_time.perf_counter() - t_flight0)
            if stats is not None:
                stats.mesh_flight = flight
        if handle is not None:
            handle.close()


def _plan_schema(node: PlanNode) -> Schema:
    return Schema([(f.name, f.type) for f in node.fields])


_DYN_TYPES = (T.BigintType, T.IntegerType, T.SmallintType, T.TinyintType,
              T.DateType)


def _apply_dynamic_bounds(probe: Batch,
                          dyn: List[Tuple[int, int, int]]) -> Batch:
    """Device-side probe prefilter: drop rows whose key cannot match any
    build row (outside [lo, hi] or NULL — inner-join semantics). Shrinks
    the join kernel's input; the scan-level pushdown handles IO."""
    keep = probe.row_mask
    for pk, lo, hi in dyn:
        c = probe.columns[pk]
        keep = keep & c.validity & (c.data >= lo) & (c.data <= hi)
    return Batch(probe.schema, probe.columns, keep)


def _residual_true(residual, cols, live, pargs):
    """(lanes of ``live`` where ``residual`` over ``cols`` is TRUE, the
    row-error scalar over ``live`` or None); traceable."""
    from ..expr.compiler import _err_scalar, _param_trace, eval_expr
    from ..expr.functions import Val
    with _param_trace([residual], pargs):
        p = eval_expr(residual, [Val(c.data, c.validity, c.type,
                                     c.dictionary) for c in cols])
    return live & p.valid & p.data, _err_scalar([p.err], live)


def _residual_payload(residual, n_src: int):
    """(build columns the residual reads, the residual over the source's
    columns followed by exactly those)."""
    from ..expr.rewrite import referenced_inputs, remap_inputs
    refs = referenced_inputs(residual)
    payload = sorted(i - n_src for i in refs if i >= n_src)
    remap = {i: i if i < n_src else n_src + payload.index(i - n_src)
             for i in refs}
    return payload, remap_inputs(residual, remap)


def mark_exists_mask(probe: Batch, build: Batch, probe_keys, build_keys,
                     residual, negated: bool, max_matches: int,
                     prepared=None, pargs=()):
    """Correlated-EXISTS mark over a build that may hold a key many
    times (the `expand` form): a probe row passes iff ANY build row with
    equal keys satisfies the residual predicate (over probe fields +
    build fields). The decorrelated mark-join shape of reference
    TransformExistsApplyToCorrelatedJoin.java: expand the m:n matches
    (slot k of every probe lane its k-th match, the build columns the
    residual reads gathered for each), evaluate the residual, and mark
    the lanes any of whose slots passed. (mask, row-error scalar or
    None); pure and traceable: the executor launches it as one named
    program (``_residual_program``), the mesh inside its own."""
    k = max(1, max_matches)
    payload, residual = _residual_payload(residual, len(probe.columns))
    expanded = expand_join(probe, build, probe_keys, build_keys, payload,
                           [f"$f{i}" for i in payload], "inner", k,
                           prepared=prepared)
    found, err = _residual_true(residual, expanded.columns,
                                expanded.row_mask, pargs)
    found = jnp.any(found.reshape(k, probe.capacity), axis=0)
    return (probe.row_mask & ~found if negated else found), err


def keyed_exists_mask(probe: Batch, build: Batch, probe_keys, residual,
                      negated: bool, prepared, packed, pargs=()):
    """The mark over a build that holds every key ONCE (the `keyed`
    form): a probe row's one match is looked up, the build columns the
    residual reads come from ``packed`` (``ops.join.pack_sorted_payload``)
    in one gather, and the residual is decided on that row. Nothing is
    expanded. (mask, row-error scalar or None)."""
    from ..ops.join import keyed_match
    payload, residual = _residual_payload(residual, len(probe.columns))
    cols, match = keyed_match(probe, build, probe_keys, payload, prepared,
                              packed)
    found, err = _residual_true(residual, list(probe.columns) + cols,
                                match, pargs)
    return (probe.row_mask & ~found if negated else found), err


_RESIDUAL_PROGRAMS: Dict[tuple, object] = {}


def _residual_program(form: str, node: SemiJoinNode, probe_schema: Schema,
                      max_matches: int = 1):
    """The ONE program a probe batch of a residual semi join launches:
    ``expr_semi_<form>_<digest>`` (an expression program: its digest is
    the residual's and the schemas', ``system.runtime.executables`` has
    the text, plan-template parameters arrive as traced operands).
    Called with ``(probe, build, prepared[, packed])``."""
    from ..expr.compiler import _ExprProgram
    key = (form, node.residual, probe_schema, node.source_keys,
           node.filtering_keys, node.negated, max_matches)
    fn = _RESIDUAL_PROGRAMS.get(key)
    if fn is None:
        # (the program outlives the query: it holds the residual and
        # the keys, not the node and the plan under it)
        residual, negated = node.residual, node.negated
        skeys, fkeys = list(node.source_keys), list(node.filtering_keys)

        def run(args, pargs=()):
            if form == "keyed":
                probe, build, prepared, packed = args
                return keyed_exists_mask(probe, build, skeys, residual,
                                         negated, prepared, packed, pargs)
            probe, build, prepared = args
            return mark_exists_mask(probe, build, skeys, fkeys, residual,
                                    negated, max_matches, prepared, pargs)
        fn = _RESIDUAL_PROGRAMS[key] = _ExprProgram(
            "semi_" + form, key, run, [residual])
    return fn


import functools


@functools.lru_cache(maxsize=None)
def unnest_expand_fn(exprs, ordinality: bool, schema: Schema):
    """Compiled lateral array expansion: [cap, L] element tiles flatten to
    [cap*L] rows, outer columns repeat per element slot (reference
    operator/unnest/UnnestOperator.java). Rows beyond an array's length
    are masked dead; multiple arrays zip to the longest (shorter ones
    padded with NULL elements)."""
    import jax

    from ..expr.compiler import eval_expr
    from ..expr.functions import Val

    def expand(b: Batch) -> Batch:
        inputs = [Val(c.data, c.validity, c.type, c.dictionary)
                  for c in b.columns]
        if not inputs:
            inputs = [Val(b.row_mask, b.row_mask, T.BOOLEAN)]
        arrs = [eval_expr(e, inputs) for e in exprs]
        # row-level errors raised inside the array expressions (e.g.
        # UNNEST(transform(a, x -> 1/x))) must fail the query, matching
        # compile_projection(errors=True)
        from ..expr.compiler import _err_scalar
        err_scalar = _err_scalar([a.err for a in arrs], b.row_mask)
        widths = [a.data[0].shape[1] for a in arrs]
        L = max(widths)
        cap = b.capacity
        # effective length: NULL array -> 0 rows (cross-join semantics)
        eff_lens = [jnp.where(a.valid, a.data[1], 0) for a in arrs]
        max_len = eff_lens[0]
        for ln in eff_lens[1:]:
            max_len = jnp.maximum(max_len, ln)
        slot = jnp.broadcast_to(jnp.arange(L)[None, :], (cap, L))
        out_mask = (b.row_mask[:, None] & (slot < max_len[:, None])
                    ).reshape(-1)
        cols = []
        for c in b.columns:
            data = jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, L, axis=0), c.data)
            cols.append(Column(c.type, data,
                               jnp.repeat(c.validity, L, axis=0),
                               c.dictionary))
        for a, w, ln in zip(arrs, widths, eff_lens):
            values, _, elem_valid = a.data
            if w < L:
                values = jnp.pad(values, ((0, 0), (0, L - w)))
                elem_valid = jnp.pad(elem_valid, ((0, 0), (0, L - w)))
            ev = elem_valid & (slot < ln[:, None])
            cols.append(Column(a.type.element, values.reshape(-1),
                               ev.reshape(-1), a.dictionary))
        if ordinality:
            cols.append(Column(T.BIGINT,
                               (slot + 1).astype(jnp.int64).reshape(-1),
                               out_mask, None))
        return Batch(schema, cols, out_mask), err_scalar

    # registered jit entry (not a raw @jax.jit): compile time,
    # invocations and profiled device time land in obs.profiler's
    # EXECUTABLES like every jitcache kernel, and the trace-safety lint
    # (tools/analyze/tracing.py) holds the line on new bypasses
    from ..ops.jitcache import timed_entry
    return timed_entry("unnest_expand", expand, (exprs, ordinality))


class _Executor:
    def __init__(self, session: Session, rows_per_batch: int,
                 stats=None):
        self.session = session
        self.rows_per_batch = rows_per_batch
        self.init_values: List[object] = []
        self.stats = stats
        # set by execute_plan: a threading.Event checked per scan batch
        # so a DELETE-cancel interrupts a query mid-drain
        self.cancel_event = None
        # result-cache incremental delta: {(catalog, table): predicate
        # over Split} restricting a scan to the changed splits only
        # (serving/resultcache.py); None = scan everything
        self.split_restrict = None
        # device int32 scalars from error-checking kernels; reduced to one
        # host sync by check_errors() after the plan drains
        self.error_flags: List = []
        self._shared: set = set()
        self._ever_shared: set = set()
        self._materialized: Dict[PlanNode, List[Batch]] = {}
        # node -> its already started iterator, handed to the next
        # run(node) in place of a second execution (_agg_step_states
        # pulls a source itself and gives the rest back when it declines)
        self._resumed: Dict[PlanNode, Iterator[Batch]] = {}
        # runtime (dynamic-filter) scan bounds: scan node -> [(col, lo, hi)]
        self.dynamic_pushdown: Dict[PlanNode, List[Tuple]] = {}
        # grouped (lifespan) execution: scan node -> the split list it
        # is currently restricted to (one bucket's files; reference
        # execution/Lifespan.java:26 + scheduler/group/LifespanScheduler)
        self.lifespan_splits: Dict[PlanNode, List] = {}
        from ..memory import QueryMemoryPool

        def _int_prop(name, default=None):
            v = session.properties.get(name, default)
            return int(v) if v is not None else None
        self.pool = QueryMemoryPool(
            _int_prop("query_max_memory"),
            # second spill tier: staged host bytes beyond this flush to
            # compressed pages on disk (reference NodeSpillConfig)
            disk_threshold=_int_prop("spill_to_disk_bytes", 4 << 30),
            spill_dir=session.properties.get("spill_path"),
            # admitted queries mirror reservations to their resource
            # group's ledger (kill-or-queue on group memory limits)
            group=getattr(session, "serving", None))
        self.spill_partitions = int(
            session.properties.get("spill_partitions", 16))
        session.last_memory_stats = self.pool.stats

    def _check_cancel(self) -> None:
        ev = self.cancel_event
        if ev is not None and ev.is_set():
            from ..errors import QueryCancelledError
            raise QueryCancelledError()

    def checked_filter(self, pred: ir.Expr, schema: Schema):
        """Compiled filter that feeds row errors into this query's
        error_flags (for predicates applied outside Filter nodes, e.g.
        join ON residuals)."""
        fn = compile_filter(pred, schema, errors=True)

        def run(b: Batch) -> Batch:
            out, err = fn(b)
            if err is not None:
                self.error_flags.append(err)
            return out
        return run

    def check_errors(self) -> None:
        """Raise the highest-coded row error seen by any kernel this query
        (one host sync over all collected device scalars)."""
        if not self.error_flags:
            return
        import numpy as np

        from ..errors import QueryError
        codes = np.asarray(device_sync(
            "error-flags", jnp.stack(self.error_flags)))
        self.error_flags = []
        code = int(codes.max())
        if code:
            raise QueryError(code)

    def mark_shared(self, roots: Sequence[PlanNode]) -> None:
        """Pre-scan for structurally repeated subplans (e.g. the shared
        input of a GROUPING SETS union): their output is materialized once
        and replayed — the executor-side equivalent of the reference's
        single-pass GroupIdOperator over a shared source."""
        from collections import Counter
        counts: Counter = Counter()

        def walk(n: PlanNode) -> None:
            counts[n] += 1
            if counts[n] > 1:
                return
            for c in n.children:
                walk(c)

        for r in roots:
            walk(r)
        self._shared = {n for n, c in counts.items() if c > 1}
        # never-discarded copy: dynamic-filter pushdown must see a
        # subtree as multi-consumer even after its memo was abandoned
        # under memory pressure (run() discards from _shared then)
        self._ever_shared = set(self._shared)

    # -- expression preparation ---------------------------------------------
    def _resolve(self, e: ir.Expr) -> ir.Expr:
        def fn(n: ir.Expr) -> ir.Expr:
            if isinstance(n, ir.Literal) and isinstance(n.value, InitPlanRef):
                return ir.Literal(type=n.type,
                                  value=self.init_values[n.value.index])
            return n
        return fold_expr(ir_rewrite(e, fn)) if self.init_values else e

    # -- dispatch -------------------------------------------------------------
    def run(self, node: PlanNode) -> Iterator[Batch]:
        if self._resumed and node in self._resumed:
            return self._resumed.pop(node)
        if node in self._materialized:
            # cache replay: the node's stats already recorded the one real
            # execution — don't re-wrap or double-count
            return iter(self._materialized[node])
        m = getattr(self, "_" + type(node).__name__)
        if node in self._shared:
            return self._run_memoized(node, m)
        it = m(node)
        if self.stats is not None:
            it = self.stats.wrap(node, it)
        if TRACER.enabled:
            # operator span: first batch to exhaustion, inclusive of
            # children (the printer/Chrome viewer nests them by time)
            it = TRACER.wrap_iter(
                "op:" + type(node).__name__.replace("Node", ""), it)
        return it

    def _run_memoized(self, node: PlanNode, m) -> Iterator[Batch]:
        """Materialize a shared subplan's output once, within the memory
        budget: each cached batch reserves from the query pool, and if the
        pool can't hold the next batch the cache is abandoned (repeat
        consumers re-execute instead of OOMing device memory)."""
        from .spill import batch_device_bytes
        ctx = self.pool.context(f"memo-{type(node).__name__}")
        it = m(node)
        if self.stats is not None:
            it = self.stats.wrap(node, it)
        if TRACER.enabled:
            it = TRACER.wrap_iter(
                "op:" + type(node).__name__.replace("Node", ""), it,
                memoized=True)
        out: List[Batch] = []
        for b in it:
            if not ctx.pool.try_reserve(batch_device_bytes(b), ctx):
                # over budget: abandon the cache; this consumer streams on
                # and later consumers re-execute the subplan
                ctx.release_all()
                self._shared.discard(node)
                return itertools.chain(out, [b], it)
            out.append(b)
        self._materialized[node] = out
        return iter(out)

    def _OutputNode(self, node: OutputNode) -> Iterator[Batch]:
        return self.run(node.child)

    # -- leaves ---------------------------------------------------------------
    def _scan_pushdown_fn(self, node: TableScanNode):
        """Closure yielding a scan's EFFECTIVE pushdown, re-evaluated
        per split: dynamic (join build) bounds may arrive while earlier
        splits are already streaming — later splits still benefit (the
        reference's dynamic filters race the probe scan the same way).
        Shared with the cluster worker's task executor."""
        def current_pushdown():
            pushdown = node.pushdown or None
            dyn = self.dynamic_pushdown.get(node)
            if dyn:
                # intersect per column: connectors keep one bound per
                # name, so appending would let a wider dynamic bound
                # shadow a tighter WHERE-derived one
                merged: Dict[str, List] = {}
                for name, lo, hi in list(pushdown or ()) + dyn:
                    b = merged.setdefault(name, [lo, hi])
                    if lo is not None:
                        b[0] = lo if b[0] is None else max(b[0], lo)
                    if hi is not None:
                        b[1] = hi if b[1] is None else min(b[1], hi)
                pushdown = tuple((n, lo, hi)
                                 for n, (lo, hi) in merged.items())
            return pushdown
        return current_pushdown

    def _TableScanNode(self, node: TableScanNode) -> Iterator[Batch]:
        """Split-parallel scan through the device scan cache + async
        prefetching pipeline (exec/scancache.py): hot split data replays
        from device memory across queries, cold splits decode/stage on
        background threads ahead of the consumer so device compute
        overlaps input production — the role of the reference's split
        pipeline (execution/SqlTaskExecution.java:390 one driver per
        split + BufferingSplitSource prefetch).

        Delivery is in deterministic split order (per-split reorder
        queues): physical row order feeds order-sensitive downstream
        semantics (ROWS window frames with ties, LIMIT-without-ORDER),
        so prefetch must not reshuffle it run to run."""
        from . import scancache

        conn = self.session.catalogs.get(node.catalog)
        current_pushdown = self._scan_pushdown_fn(node)
        opts = scancache.options_from_session(self.session)
        splits = conn.split_manager.splits(
            node.table, max(opts.threads, 1))
        lifespan = self.lifespan_splits.get(node)
        if lifespan is not None:
            # grouped execution: only this bucket's splits this pass
            splits = lifespan
        restrict = getattr(self, "split_restrict", None)
        if restrict is not None:
            pred = restrict.get((node.catalog, node.table.table))
            if pred is not None:
                # result-cache delta run: only the changed splits
                splits = [s for s in splits if pred(s)]
        import time as _time
        t_query0 = _time.perf_counter()

        def record_split(i: int, t0: float, batches: int) -> None:
            # per-split completion record (reference event/SplitMonitor)
            if self.stats is not None:
                self.stats.record_split(
                    node.table.table, i, t0 - t_query0,
                    _time.perf_counter() - t0, batches)

        yield from scancache.scan_splits(
            conn, node.catalog, list(node.columns), splits,
            current_pushdown, self.rows_per_batch, opts,
            record_split=record_split, check_cancel=self._check_cancel,
            stats=self.stats, static_pushdown=node.pushdown or None)

    def _ValuesNode(self, node: ValuesNode) -> Iterator[Batch]:
        data = {
            f.name: (f.type, [r[i] for r in node.rows])
            for i, f in enumerate(node.fields)
        }
        if node.fields:
            yield Batch.from_pydict(data)
            return
        # zero-column values (SELECT without FROM): a 1-row dummy column
        n = len(node.rows)
        mask = jnp.arange(bucket_capacity(max(n, 1))) < n
        yield Batch(Schema([]), [], mask)

    # -- streaming nodes ------------------------------------------------------
    compact_streams = True   # DistributedExecutor turns this off: compact()
    #                          on a mesh-sharded batch would gather it

    def _compactor(self):
        """Per-operator adaptive compaction (one host sync per checked
        batch): the analogue of Presto's compacted filter output pages
        (reference operator/project/PageProcessor.java). Selective
        filters/joins leave mostly-dead lanes, and every downstream
        sort-based kernel pays for capacity, not liveness. Checks batches
        >128K capacity; after the first batch that doesn't shrink >=4x it
        stops checking (selectivity is near-uniform across an operator's
        batches), so a non-selective stream pays exactly one sync."""
        state = {"check": self.compact_streams}

        def maybe_compact(b: Batch) -> Batch:
            # the 2^17 floor and the 4x rule, on the v5e (PERF.md section
            # 5, tools/compact_probe.py): the readback costs ~1.0 ms, the
            # program 0.9 ms at 2^17 lanes, 3.1 (2^20 to 2^15) to 24 ms
            # (to 2^18): ~11 ns a gathered lane a column. Counted from the
            # count read here (_note_compaction, below)
            if not state["check"] or b.capacity <= (1 << 17):
                return b
            tgt = bucket_capacity(b.host_count("compaction-liveness"))
            state["check"] = shrink = tgt * 4 <= b.capacity
            _note_compaction(b.capacity, tgt if shrink else b.capacity)
            return compact_jit(b, tgt) if shrink else b
        #: whether a batch of this capacity would still be looked at
        maybe_compact.looks_at = lambda capacity: (
            state["check"] and capacity > (1 << 17))
        return maybe_compact

    def _FilterNode(self, node: FilterNode) -> Iterator[Batch]:
        pred = self._resolve(node.predicate)
        fn = compile_filter(pred, _plan_schema(node.child), errors=True)
        compact = self._compactor()
        for b in self.run(node.child):
            out, err = fn(b)
            if err is not None:
                self.error_flags.append(err)
            yield compact(out)

    def _ProjectNode(self, node: ProjectNode) -> Iterator[Batch]:
        exprs = [self._resolve(e) for e in node.exprs]
        fn = compile_projection(exprs, [f.name for f in node.fields],
                                _plan_schema(node.child), errors=True)
        for b in self.run(node.child):
            out, err = fn(b)
            if err is not None:
                self.error_flags.append(err)
            yield out

    def _LimitNode(self, node: LimitNode) -> Iterator[Batch]:
        remaining = node.count
        for b in self.run(node.child):
            if remaining <= 0:
                return
            out = limit_kernel(b, remaining)
            remaining -= out.host_count("limit-remaining")
            yield out

    def _UnionNode(self, node: UnionNode) -> Iterator[Batch]:
        for c in node.children:
            yield from self.run(c)

    def _UnnestNode(self, node) -> Iterator[Batch]:
        exprs = tuple(self._resolve(e) for e in node.exprs)
        fn = unnest_expand_fn(exprs, node.ordinality, _plan_schema(node))
        compact = self._compactor()
        for b in self.run(node.child):
            out, err = fn(b)
            if err is not None:
                self.error_flags.append(err)
            yield compact(out)

    def _GroupIdNode(self, node: GroupIdNode) -> Iterator[Batch]:
        """One replica batch per grouping set: absent keys get their
        validity cleared (NULL), $group_id is a constant column
        (reference operator/GroupIdOperator.java)."""
        schema = _plan_schema(node)
        for b in self.run(node.child):
            dead = jnp.zeros_like(b.row_mask)
            alive = jnp.ones_like(b.row_mask)
            for g, s in enumerate(node.grouping_sets):
                cols = []
                for i, c in enumerate(b.columns):
                    if i < node.n_keys and i not in s:
                        # zero data too: the group-sort uses (null-rank,
                        # data) as sort operands, so stale data under a
                        # cleared validity would still split groups
                        cols.append(Column(c.type, jnp.zeros_like(c.data),
                                           dead, c.dictionary))
                    else:
                        cols.append(c)
                cols.append(Column(
                    T.BIGINT,
                    jnp.full(b.capacity, g, dtype=jnp.int64), alive, None))
                yield Batch(schema, cols, b.row_mask)

    # -- blocking nodes -------------------------------------------------------
    def _drain(self, node: PlanNode) -> Optional[Batch]:
        batches = list(self.run(node))
        if not batches:
            return None
        return batches[0] if len(batches) == 1 else concat_batches(batches)

    def _SortNode(self, node: SortNode) -> Iterator[Batch]:
        from .spill import SortSpillBuffer
        keys = [SortKey(k.index, k.ascending, k.nulls_first)
                for k in node.keys]
        buf = SortSpillBuffer(self.pool, "order-by", keys)
        try:
            for b in self.run(node.child):
                buf.add(b)
            yield from buf.results(self.rows_per_batch)
        finally:
            buf.close()

    def _TopNNode(self, node: TopNNode) -> Iterator[Batch]:
        keys = [SortKey(k.index, k.ascending, k.nulls_first)
                for k in node.keys]
        state: Optional[Batch] = None
        for b in self.run(node.child):
            cand = compact_jit(
                top_n(b, keys, node.count), bucket_capacity(node.count))
            state = cand if state is None else compact_jit(top_n(
                concat_batches([state, cand]), keys, node.count),
                    bucket_capacity(node.count))
        if state is not None:
            yield sort_batch(state, keys)

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
        out = evaluate_window(b, list(node.partition_indices), keys, specs)
        yield Batch(_plan_schema(node), out.columns, out.row_mask)

    def _MarkDistinctNode(self, node) -> Iterator[Batch]:
        """Drain + sort-based first-occurrence flags (the window/sort
        drain pattern; reference MarkDistinctOperator keeps a hash set
        across pages instead)."""
        from ..ops.aggregation import mark_distinct_flags
        b = self._drain(node.child)
        if b is None:
            return
        flags = mark_distinct_flags(b, list(node.cols))
        mark_col = Column(T.BOOLEAN, flags, b.row_mask, None)
        yield Batch(_plan_schema(node), list(b.columns) + [mark_col],
                    b.row_mask)

    def _grouped_partial_fn(self, group, aggs, kb, ordered=False):
        """Per-batch partial aggregation with the stats-bounds contract:
        record which kernel the grouping takes (once — the dispatch is
        shape-stable across an operator's batches), and when static key
        bounds are in play, append the device-side violation scalar to the
        error channel so a connector overclaiming its statistics fails the
        query instead of silently misgrouping (one sync per query).
        ``ordered``: the planner promises every batch in the keys' order
        (AggregationNode.ordered_input): the sort path compiles no sort,
        and the scalar that says whether the batch kept the promise
        goes the same way."""
        from ..ops.aggregation import dense_path_selected
        allow = bool_property(self.session, "dense_grouping", True)
        seen = {}

        def partial(b: Batch) -> Tuple[Batch, bool]:
            # per-batch dispatch mirror: only batches that actually take
            # the dense path clamp out-of-bounds keys, so only those
            # batches owe a violation flag — the sort path groups any
            # key correctly and must not fail on overclaimed stats
            dense = allow and dense_path_selected(b, group, aggs,
                                                  key_bounds=kb)
            if not seen:
                seen["done"] = True
                (_AGG_DENSE_SELECTED if dense
                 else _AGG_SORT_SELECTED).inc()
            if dense and kb is not None:
                self.error_flags.append(
                    key_bounds_violation_jit(b, group, kb))
            # (the state, whether the sort path made it: live rows
            # first and in the sort's order, which the state's merges
            # build on)
            state = grouped_aggregate(b, group, aggs, mode="partial",
                                      key_bounds=kb, allow_dense=allow,
                                      ordered=ordered)
            if ordered:
                state, out_of_order = state
                self.error_flags.append(out_of_order)
            return state, not dense
        return partial

    def _expr_stage(self, nd: PlanNode):
        """The fused stage (exec/fused.py) of a Filter or Project node,
        init-plan references resolved."""
        from .fused import FilterStage, ProjectStage
        if isinstance(nd, FilterNode):
            return FilterStage(self._resolve(nd.predicate))
        return ProjectStage(tuple(self._resolve(e) for e in nd.exprs),
                            tuple(f.name for f in nd.fields))

    def _agg_step_chain(self, node: AggregationNode, aggs):
        """What the aggregation sink (exec/fused.py agg_step) would fuse
        under this grouped aggregation that computes partials (a single
        or partial step): (stages bottom-up, source node),
        or None where the operator cannot take it whatever its batches
        hold. The chain is the Filter/Project nodes directly under the
        aggregation; the rest of the rule waits for a batch
        (_agg_step_states)."""
        from ..ops.aggregation import _wide_state_aggs
        if (not self.compact_streams or _wide_state_aggs(aggs)
                or not bool_property(self.session, "dense_grouping", True)
                or int(self.session.properties.get(
                    "task_concurrency", 1)) > 1):
            return None
        child = _plan_schema(node.child)
        if not all(child.types[g].is_string or child.types[g] == T.BOOLEAN
                   for g in node.group_indices):
            # an integer key groups densely only by its stats bounds,
            # which is the scatter path: not the sink's
            return None
        stages: List[object] = []
        cur = node.child
        while isinstance(cur, (FilterNode, ProjectNode)):
            if cur in self._shared or cur in self._materialized:
                return None              # memoized: it runs standalone
            stages.append(self._expr_stage(cur))
            cur = cur.child
        from ..expr.params import has_params
        if has_params(stages):
            # plan-template parameters: the step traces expressions
            # inside its own jit with no operand channel for runtime
            # bindings (the rule of _try_fused_chain)
            return None
        return tuple(reversed(stages)), cur

    def _agg_step_states(self, node: AggregationNode, chain, group, aggs,
                         kb) -> Iterator[Batch]:
        """Partial states of a grouped aggregation through the sink: the
        source's batches fold into ONE running state, a launch a batch,
        and the state comes out at the end (and before a batch whose
        dictionaries give it another layout). A batch whose grouping
        does not take the small dense path hands it and the rest of the
        source back to the per-operator path. The interior Filter/Project
        nodes never run standalone: EXPLAIN ANALYZE shows their work on
        the aggregation, as the join fusion does."""
        from .fused import agg_step, agg_step_finish, agg_step_start
        stages, source = chain
        key = (stages, tuple(group), tuple(aggs), kb)
        it = iter(self.run(source))
        state = layout = fn = sig = None
        folded = False
        for b in it:
            leaves, treedef = jax.tree_util.tree_flatten(b)
            bsig = (treedef, tuple((x.shape, x.dtype) for x in leaves))
            if bsig != sig:
                start = agg_step_start(*key, *bsig)
                if start is None:
                    self._resumed[source] = itertools.chain([b], it)
                    break
                sig = bsig
                if state is not None and start[0] != layout:
                    _AGG_STEP_FLUSHES.inc()
                    yield agg_step_finish(layout)(state), False
                    state = None
                if state is None:
                    layout, state = start
                    fn = agg_step(*key, layout)
            if not folded:
                folded = True
                _AGG_STEP_SELECTED.inc()
                _AGG_DENSE_SELECTED.inc()
            state, err = fn(state, b)
            if err is not None:
                self.error_flags.append(err)
            _AGG_STEP_BATCHES.inc()
        if state is not None:
            yield agg_step_finish(layout)(state), False
        if not folded:
            _AGG_STEP_DECLINED.inc()     # no batch at all counts here too
        if source in self._resumed:
            partial = self._grouped_partial_fn(group, aggs, kb)
            for b in self.run(node.child):
                yield partial(b)

    def _DistinctNode(self, node: DistinctNode) -> Iterator[Batch]:
        from .spill import AggSpillBuffer
        cols = list(range(len(node.fields)))
        kb = tuple(node.key_bounds) if node.key_bounds else None
        buf = AggSpillBuffer(
            self.pool, "distinct", cols, [], self.spill_partitions,
            key_bounds=kb,
            allow_dense=bool_property(self.session, "dense_grouping",
                                      True),
            error_sink=self.error_flags.append)
        partial = self._grouped_partial_fn(cols, [], kb)
        try:
            for b in self.run(node.child):
                state, normalized = partial(b)
                buf.add_partial(state, normalized=normalized)
            yield from buf.results()
        finally:
            buf.close()

    def _AggregationNode(self, node: AggregationNode) -> Iterator[Batch]:
        aggs = [
            AggSpec(a.fn, a.arg, a.output_type, a.name, mask=a.mask,
                    param=a.param)
            for a in node.aggs
        ]
        for a in node.aggs:
            if a.distinct:
                raise NotImplementedError(
                    "DISTINCT aggregates must be lowered by the planner")
        group = list(node.group_indices)
        from ..ops.aggregation import percentile_drains
        # final-step nodes consume STATE columns (the fragmenter decided
        # drain-vs-sketch before splitting; agg input indices reference
        # the raw child, not the state layout) — never re-check them
        if node.step != "final" and \
                percentile_drains(aggs, _plan_schema(node.child).types,
                                  bool(group)):
            # grouped/string approx_percentile: no mergeable state —
            # drain the input and evaluate in one exact segmented-sort
            # pass (global numeric forms carry bounded qdigest-style
            # histogram state through the normal partial/final path
            # below instead)
            b = self._drain(node.child)
            if b is None:
                if group:
                    return
                b = Batch.from_arrays(
                    _plan_schema(node.child),
                    [[] for _ in node.child.fields], num_rows=0)
            if group:
                yield grouped_aggregate(b, group, aggs, mode="single")
            else:
                yield global_aggregate(b, aggs, mode="single")
            return
        # fragment steps (reference plan/AggregationNode.Step): SINGLE
        # raw->rows; PARTIAL raw->states (shipped to an exchange); FINAL
        # states->rows.  step never changes the kernels, only which side
        # of the state boundary this node covers.
        step = node.step
        if not group:
            # sketch aggregates carry wide state tiles ([cap, m]
            # registers / [cap, bins] histograms); merge them eagerly so
            # peak memory stays a few tiles, not 64
            merge_at = 4 if any(a.fn in ("approx_distinct",
                                         "approx_percentile")
                                for a in aggs) else 64
            parts: List[Batch] = []
            for b in self.run(node.child):
                parts.append(_global_partial(b, aggs)
                             if step != "final" else b)
                if len(parts) >= merge_at:
                    parts = [global_aggregate(_global_states(parts), aggs,
                                              mode="merge")]
            if not parts:
                # no input still finalizes to one row (count=0): final
                # mode reduces a 0-row state batch; other steps reduce a
                # 0-row raw batch into an explicit empty partial
                empty = Batch.from_arrays(
                    _plan_schema(node.child),
                    [[] for _ in node.child.fields], num_rows=0)
                parts = [empty if step == "final"
                         else global_aggregate(empty, aggs,
                                               mode="partial")]
            states = _global_states(parts)
            if step == "partial":
                yield global_aggregate(states, aggs, mode="merge") \
                    if len(parts) > 1 else states
            else:
                yield global_aggregate(states, aggs, mode="final")
            return
        # grouped: partial per input batch, hierarchical merge (spillable
        # state, hash-partitioned by group keys under memory pressure),
        # final per state / per spilled partition. With task_concurrency
        # > 1, partials run on N driver threads over a round-robin local
        # exchange (reference AddLocalExchanges + multi-driver pipelines)
        from .local_exchange import parallel_drivers
        from .spill import AggSpillBuffer
        key_idx = list(range(len(group)))
        kb = tuple(node.key_bounds) if node.key_bounds else None
        buf = AggSpillBuffer(
            self.pool, "hash-agg", key_idx, aggs, self.spill_partitions,
            key_bounds=kb,
            allow_dense=bool_property(self.session, "dense_grouping",
                                      True),
            error_sink=self.error_flags.append)
        concurrency = int(self.session.properties.get(
            "task_concurrency", 1))
        try:
            chain = (None if step == "final"
                     else self._agg_step_chain(node, aggs))
            if step == "final":
                # what an exchange hands over may hold several
                # producers' states, a key more than once
                for p in self.run(node.child):
                    buf.add_partial(p, unique=False)
                partials = ()
            elif chain is not None:
                partials = self._agg_step_states(node, chain, group, aggs,
                                                 kb)
            else:
                _AGG_STEP_DECLINED.inc()
                partials = parallel_drivers(
                    self.run(node.child),
                    self._grouped_partial_fn(group, aggs, kb,
                                             node.ordered_input),
                    concurrency)
            for p, normalized in partials:
                buf.add_partial(p, normalized=normalized)
            if node.default_gids and step in ("single", "final"):
                # grouping sets over EMPTY input: the empty sets still
                # owe their grand-total rows (reference
                # AggregationNode.hasDefaultOutput); detect zero output
                # groups (aggregated outputs are small, so the host
                # count is cheap) and synthesize them
                outs = list(buf.results(final=True))
                live = sum(b.host_count() for b in outs)
                yield from outs
                if live == 0:
                    yield _default_grouping_batch(node)
            else:
                yield from buf.results(final=step != "partial")
        finally:
            buf.close()

    def _lifespan_partitions(self, node: JoinNode):
        """Partition-wise (grouped / lifespan) execution check: when both
        join sides scan hive-partitioned tables whose partition keys are
        covered pairwise by the equi-join keys, rows only ever match
        within equal partition values — so the join can run one bucket
        at a time, bounding peak HBM at O(bucket) instead of O(table)
        (reference execution/Lifespan.java:26,
        execution/scheduler/group/LifespanScheduler.java,
        PipelineExecutionStrategy.GROUPED_EXECUTION).

        Returns (left_scan, right_scan, ordered common partition value
        tuples) or None."""
        if node.join_type != "inner":
            return None
        if not bool_property(self.session, "grouped_execution", True):
            return None

        def unwrap(n):
            while isinstance(n, FilterNode):
                n = n.child
            return n if isinstance(n, TableScanNode) else None

        ls, rs = unwrap(node.left), unwrap(node.right)
        if ls is None or rs is None or ls is rs:
            return None
        # memoized (shared-subtree) scans cache their first bucket's
        # output; never lifespan-restrict them
        if any(n in self._ever_shared
               for n in (ls, rs, node.left, node.right)):
            return None

        def partition_info(scan):
            conn = self.session.catalogs.get(scan.catalog)
            keys_fn = getattr(conn, "partition_keys", None)
            if keys_fn is None:
                return None
            keys = keys_fn(scan.table.table)
            if not keys:
                return None
            try:
                idx = [scan.columns.index(k) for k in keys]
            except ValueError:
                return None     # partition column not even scanned
            # one split enumeration per side; bucket selection later is
            # a dict lookup, not a directory re-walk per bucket
            by_value: Dict[Tuple, List] = {}
            for s in conn.split_manager.splits(scan.table, 1):
                if len(s.info) > 1:
                    by_value.setdefault(tuple(s.info[1]), []).append(s)
            return idx, by_value

        li, ri = partition_info(ls), partition_info(rs)
        if li is None or ri is None or len(li[0]) != len(ri[0]):
            return None
        # every partition-key position must be an equi-join pair
        pairs = set(zip(node.left_keys, node.right_keys))
        if any((lk, rk) not in pairs
               for lk, rk in zip(li[0], ri[0])):
            return None
        common = sorted(li[1].keys() & ri[1].keys())
        return ls, rs, [(li[1][v], ri[1][v]) for v in common]

    def _coalesce(self, it: Iterator[Batch],
                  min_cap: int = 1 << 15) -> Iterator[Batch]:
        """Merge runs of small batches into fewer larger ones. Selective
        joins compact their outputs to tiny buckets, and every
        downstream operator then pays its dispatch PER BATCH for little
        kernel work (the ratio is not measured on the v5e). Capacity (not a
        live-count sync) decides: batches at or above min_cap pass
        through, smaller ones buffer until their capacities sum past it
        (the role of the reference's PageBuffer/page coalescing in
        exchange clients)."""
        pend: List[Batch] = []
        acc = 0
        for b in it:
            if b.capacity >= min_cap:
                if pend:
                    yield (pend[0] if len(pend) == 1
                           else concat_batches(pend))
                    pend, acc = [], 0
                yield b
                continue
            pend.append(b)
            acc += b.capacity
            if acc >= min_cap:
                yield concat_batches(pend)
                pend, acc = [], 0
        if pend:
            yield pend[0] if len(pend) == 1 else concat_batches(pend)

    def _JoinNode(self, node: JoinNode) -> Iterator[Batch]:
        yield from self._coalesce(self._join_dispatch(node))

    def _join_dispatch(self, node: JoinNode) -> Iterator[Batch]:
        lifespan = self._lifespan_partitions(node)
        if lifespan is None and bool_property(self.session,
                                              "fused_pipeline", True):
            fused = self._try_fused_chain(node)
            if fused is not None:
                yield from fused
                return
        if lifespan is not None:
            ls, rs, buckets = lifespan
            for lsplits, rsplits in buckets:
                self.lifespan_splits[ls] = lsplits
                self.lifespan_splits[rs] = rsplits
                # dynamic-filter bounds are bucket-local: bounds pushed
                # while joining bucket k must not prune bucket k+1
                saved_dyn = dict(self.dynamic_pushdown)
                try:
                    yield from self._join_once(node)
                finally:
                    self.lifespan_splits.pop(ls, None)
                    self.lifespan_splits.pop(rs, None)
                    self.dynamic_pushdown = saved_dyn
            return
        yield from self._join_once(node)

    def _try_fused_chain(self, top: JoinNode):
        """Head check for whole-pipeline fusion (exec/fused.py): a chain
        of unique-build inner/left lookup joins with interleaved filters
        and projections over one streaming source fuses into ONE jitted
        program per probe batch. Returns the output iterator, or None
        when the shape doesn't qualify — skewed/non-unique builds,
        residual predicates, FULL OUTER, cross joins, shared interior
        subtrees — in which case the generic per-operator path runs
        unchanged. EXPLAIN ANALYZE attributes the fused chain's work to
        the head join (interior nodes never execute standalone)."""
        def join_ok(j: PlanNode) -> bool:
            return (isinstance(j, JoinNode)
                    and j.join_type in ("inner", "left")
                    and j.build_unique and j.residual is None)

        if not join_ok(top):
            return None
        nodes: List[PlanNode] = []       # top-down
        cur: PlanNode = top
        njoins = 0
        while True:
            if cur is not top and cur in self._shared:
                break                    # memoized source boundary
            if join_ok(cur):
                nodes.append(cur)
                njoins += 1
                cur = cur.left
            elif isinstance(cur, (FilterNode, ProjectNode)):
                nodes.append(cur)
                cur = cur.child
            else:
                break
        if njoins < 2:
            return None
        from ..expr.params import has_params
        if any(has_params(getattr(n, "predicate", None))
               or has_params(getattr(n, "exprs", None))
               for n in nodes):
            # plan-template parameters: the fused head/tail programs
            # trace expressions inside their own jits with no operand
            # channel for runtime bindings — run the generic
            # per-operator path (compile_filter/compile_projection
            # carry the bindings there)
            return None
        return self._run_fused_chain(nodes, cur)

    def _run_fused_chain(self, nodes: List[PlanNode], source: PlanNode):
        """Drain + prepare every build in the chain (bottom-up), push all
        dynamic-filter bounds to the source scan BEFORE it starts (the
        generic path can only push the bottom join's bounds), then stream
        the probe source through the fused programs.

        Selectivity-first execution: the HEAD program applies every
        hoistable key-bounds mask plus the first join's membership mask
        over the raw source lanes — no payload gathers — and carries the
        surviving-lane count as a traced scalar. The executor syncs a
        WINDOW of those counts in one readback, compacts each surviving
        batch to its live bucket, and only then runs the TAIL program
        (all the joins' payload gathers) over the compacted lanes. The
        greedy join order already put the most selective join first
        (planner selectivity ranking), so on a q27-shaped star chain the
        payload gathers touch ~1% of the source lanes instead of all
        2^20, and the per-probe-batch liveness RTT is amortized to
        1/window."""
        from .fused import JoinStage, fused_pipeline, fused_prefilter

        order = list(reversed(nodes))
        # current-schema index -> source-schema index (for scan pushdown)
        src_map = {i: i for i in range(len(source.fields))}
        scan_target = self._dynamic_scan_target(source) \
            if isinstance(source, TableScanNode) else None
        dyn_enabled = bool_property(self.session,
                                    "enable_dynamic_filtering", True)
        stages: List[object] = []
        preps: List[object] = []
        builds: List[Batch] = []
        dyns: List[jnp.ndarray] = []
        bufs: List = []
        pre_rows: List[Tuple[int, int, int]] = []

        def close_bufs() -> None:
            for bf in bufs:
                bf.close()

        try:
            ok = self._drain_fused_builds(
                order, src_map, scan_target, dyn_enabled, stages, preps,
                builds, dyns, bufs, pre_rows)
        except BaseException:
            close_bufs()
            raise
        if not ok:
            close_bufs()
            return None

        first_join = next(i for i, st in enumerate(stages)
                          if isinstance(st, JoinStage))
        head, tail = stages[:first_join], stages[first_join:]
        join1 = tail[0]
        semi_keys = ((join1.lkeys, join1.rkeys)
                     if join1.join_type == "inner" else None)
        pre_keys = tuple(k for k, _, _ in pre_rows)
        pre_vals = jnp.asarray([[lo, hi] for _, lo, hi in pre_rows],
                               dtype=jnp.int64).reshape(len(pre_rows), 2)
        fn_head = fused_prefilter(tuple(head), pre_keys, semi_keys)
        fn_tail = fused_pipeline(tuple(tail))
        preps_t, builds_t, dyns_t = tuple(preps), tuple(builds), tuple(dyns)
        window = max(1, int(self.session.properties.get(
            "fused_compact_window", 4)))
        joins = tuple(st for st in tail if isinstance(st, JoinStage))
        return self._stream_fused(fn_head, fn_tail, source, pre_vals,
                                  preps_t, builds_t, dyns_t, window,
                                  close_bufs, joins)

    def _stream_fused(self, fn_head, fn_tail, source, pre_vals, preps_t,
                      builds_t, dyns_t, window,
                      close_bufs, joins) -> Iterator[Batch]:
        """Head -> windowed compaction -> tail streaming loop. One
        liveness readback per ``window`` probe batches (the head carries
        each batch's live count as a traced scalar); the check disables
        itself after a window with no >=4x shrink, mirroring
        _compactor's adaptive semantics, so a non-selective chain pays
        exactly one sync."""
        import numpy as np

        from ..ops.jitcache import compact_jit
        compact = self._compactor()
        state = {"check": self.compact_streams}
        pend: List[Tuple[Batch, jnp.ndarray]] = []
        # same 2^17 floor as _compactor: below it the tail kernels over
        # uncompacted capacity cost less than the (already amortized)
        # liveness RTT. Session-overridable so tests exercise the path
        # at CPU-friendly sizes.
        floor = int(self.session.properties.get(
            "fused_compact_floor", 1 << 17))

        def drain_pend() -> List[Batch]:
            if not pend:
                return []
            counts = np.asarray(device_sync(
                "fused-liveness", jnp.stack([c for _, c in pend]),
                batches=len(pend)))
            outs, shrunk = [], False
            for (b, _), live in zip(pend, counts):
                tgt = bucket_capacity(max(int(live), 1))
                if b.capacity > floor and tgt * 4 <= b.capacity:
                    b = compact_jit(b, tgt)
                    shrunk = True
                outs.append(b)
            if not shrunk:
                # selectivity is near-uniform across a chain's batches:
                # nothing shrank this window, so later windows won't
                state["check"] = False
            pend.clear()
            return outs

        def run_tail(hb: Batch) -> Iterator[Batch]:
            _FUSED_TAIL_LANES.inc(hb.capacity)
            # a lookup join keeps its probe's capacity: every stage of
            # the tail is traced at the head batch's lanes
            for st, build in zip(joins, builds_t):
                _note_payload_form(hb.capacity, build.capacity,
                                   len(st.payload), st.pallas)
            # a Pallas stage that fails to lower fails the query with
            # the compiler's message (join_pallas_probe, ops/pallas_join)
            out, err = fn_tail(hb, preps_t, builds_t, dyns_t)
            if err is not None:
                self.error_flags.append(err)
            yield compact(out)

        try:
            for probe in self.run(source):
                _FUSED_SOURCE_LANES.inc(probe.capacity)
                hb, err, cnt = fn_head(probe, pre_vals, builds_t[0],
                                       preps_t[0])
                if err is not None:
                    self.error_flags.append(err)
                if not state["check"] or hb.capacity <= floor:
                    # sub-floor batches can never compact (the tail over
                    # their full capacity costs less than the readback):
                    # bypass the window WITHOUT syncing or tripping the
                    # adaptive disable, mirroring _compactor's skip
                    yield from run_tail(hb)
                    continue
                pend.append((hb, cnt))
                if len(pend) >= window:
                    for b in drain_pend():
                        yield from run_tail(b)
            for b in drain_pend():
                yield from run_tail(b)
        finally:
            close_bufs()

    def _drain_fused_builds(self, order, src_map, scan_target, dyn_enabled,
                            stages, preps, builds, dyns, bufs,
                            pre_rows) -> bool:
        """Drain + prepare every build of a fused chain, appending to the
        caller's lists; False = shape disqualified (empty/spilled build),
        fall back to the generic path. ``pre_rows`` collects every
        dynamic-filter bound that maps to a raw source column —
        (source index, lo, hi) — for the head program's
        before-any-gathers mask."""
        from .fused import JoinStage
        from .spill import HostPartitionStore, SpillableBuildBuffer

        for nd in order:
            if isinstance(nd, FilterNode):
                stages.append(self._expr_stage(nd))
                continue
            if isinstance(nd, ProjectNode):
                stages.append(self._expr_stage(nd))
                new_map = {}
                for out_i, e in enumerate(stages[-1].exprs):
                    if isinstance(e, ir.InputRef) and e.index in src_map:
                        new_map[out_i] = src_map[e.index]
                src_map = new_map
                continue
            # JoinStage: drain + prepare this build now (through the
            # spillable buffer for memory accounting; a build the pool
            # forces to host can't fuse — generic path re-drains it)
            buf = SpillableBuildBuffer(self.pool, "join-build",
                                       list(nd.right_keys),
                                       self.spill_partitions)
            bufs.append(buf)
            for b in self.run(nd.right):
                buf.add(b)
            build = buf.finish()
            if build is None or isinstance(build, HostPartitionStore):
                return False             # empty/spilled: generic path
            summary = self._build_summary(build, nd.right_keys)
            if int(summary[0]) == 0:
                return False
            scap = bucket_capacity(max(int(summary[0]), 1))
            if scap < build.capacity:
                from ..ops.jitcache import compact_jit
                build = compact_jit(build, scap)
            prep = self._prepare_join_build(build, nd.right_keys,
                                            summary=summary,
                                            key_bounds=nd.key_bounds)
            from ..ops import pallas_join as PJ
            from ..ops.join import lookup_form
            payload_cols = tuple(range(len(nd.right.fields)))
            use_pallas = (self._pallas_probe_on()
                          and PJ.supports_join(prep, build,
                                               payload_cols))
            _note_join_strategy(
                self.stats, nd, lookup_form(prep),
                nd.distribution)
            dyn_keys: Tuple[int, ...] = ()
            dyn_val = jnp.zeros((0, 2), dtype=jnp.int64)
            if nd.join_type == "inner" and dyn_enabled:
                bounds = self._summary_bounds(summary, nd.left_keys)
                if bounds:
                    dyn_keys = tuple(k for k, _, _ in bounds)
                    dyn_val = jnp.asarray([[lo, hi]
                                           for _, lo, hi in bounds],
                                          dtype=jnp.int64)
                    for k, lo, hi in bounds:
                        # bounds whose key survives untouched back to the
                        # raw source schema hoist to the head program's
                        # pre-gather mask (selectivity-first)
                        si = src_map.get(k)
                        if si is not None:
                            pre_rows.append((si, lo, hi))
                    if scan_target is not None:
                        scan, smap = scan_target
                        extra = []
                        for k, lo, hi in bounds:
                            si = src_map.get(k)
                            si = smap.get(si) if si is not None else None
                            if si is not None:
                                extra.append((scan.columns[si], lo, hi))
                        if extra:
                            self.dynamic_pushdown.setdefault(
                                scan, []).extend(extra)
            stages.append(JoinStage(
                lkeys=tuple(nd.left_keys), rkeys=tuple(nd.right_keys),
                payload=payload_cols,
                names=tuple(f"$b{i}"
                            for i in range(len(nd.right.fields))),
                join_type=nd.join_type,
                out_fields=tuple((f.name, f.type) for f in nd.fields),
                dyn_keys=dyn_keys,
                pallas=use_pallas))
            preps.append(prep)
            builds.append(build)
            dyns.append(dyn_val)
        return True

    def _join_once(self, node: JoinNode) -> Iterator[Batch]:
        payload = list(range(len(node.right.fields)))
        payload_names = [f"$b{i}" for i in payload]
        if node.join_type == "cross":
            yield from self._cross_join(node, self._drain(node.right))
            return
        residual = (self._resolve(node.residual)
                    if node.residual is not None else None)
        residual_fn = None
        residual_outer = None
        if residual is not None:
            if node.join_type in ("left", "full"):
                # ON-clause filter of an outer join: gates matches, never
                # drops probe rows (_probe_outer_residual)
                residual_outer = self.checked_filter(
                    residual, _plan_schema(node))
            else:
                residual_fn = self.checked_filter(residual,
                                                  _plan_schema(node))

        from .local_exchange import exchange_source
        from .spill import HostPartitionStore, SpillableBuildBuffer
        buf = SpillableBuildBuffer(self.pool, "join-build",
                                   list(node.right_keys),
                                   self.spill_partitions)
        # inter-pipeline overlap: start the probe side's scan/decode in a
        # background producer while the build side drains — the role of
        # the reference's concurrently-running build and probe pipelines
        # within one task (PhasedExecutionSchedule starts both stages)
        probe_ex = None
        # don't prefetch a probe whose scan a dynamic filter could prune:
        # starting the scan before the build side finishes would read the
        # splits before the bounds exist (the reference equally delays the
        # probe scan while dynamic filters are being collected)
        dyn_prunable = (
            node.join_type == "inner"
            and bool_property(self.session, "enable_dynamic_filtering",
                              True)
            and self._dynamic_scan_target(node.left) is not None)
        if (bool_property(self.session, "probe_prefetch", True)
                and not dyn_prunable):
            probe_ex = exchange_source(self.run(node.left), "single", 1,
                                       buffer_batches=4)

        def probe_stream() -> Iterator[Batch]:
            return (probe_ex.consumer(0) if probe_ex is not None
                    else self.run(node.left))
        try:
            for b in self.run(node.right):
                buf.add(b)
            build = buf.finish()
            if isinstance(build, HostPartitionStore):
                yield from self._partitioned_join(
                    node, build, payload, payload_names, residual_fn,
                    probe_stream(), residual_outer=residual_outer)
                return
            dyn = None
            summary = None
            if build is not None:
                # ONE fused readback for live count + per-key bounds:
                # every sync stalls the host until the queued device
                # work drains, so the compaction size, direct-table
                # bounds, and dynamic-filter bounds all come from the
                # same device reduction
                summary = self._build_summary(build, node.right_keys)
            if (node.join_type == "inner" and summary is not None
                    and bool_property(self.session,
                                      "enable_dynamic_filtering", True)):
                dyn = self._summary_bounds(summary, node.left_keys)
                if dyn:
                    self._push_dynamic_bounds(node.left, dyn)
            compact = self._compactor()
            # an inner probe of a unique build is cut to its matches
            # BEFORE the payload is gathered where most of it misses
            # (TPC-H Q18: 400 of 60M lines meet what the semi join left
            # of orders): the membership costs two gathers a lane, the
            # payload two a column more (~11 ns each on the v5e). The
            # compactor's rule decides from the first batch: a probe
            # that does not shrink fourfold is probed whole from then on
            narrow = (self._compactor()
                      if node.join_type == "inner" and node.build_unique
                      and residual_outer is None else None)
            track_full = node.join_type == "full" and build is not None
            build_matched = None
            full_acc = ({"m": None} if track_full
                        and residual_outer is not None else None)
            if build is not None:
                # compact a sparse build before sorting it: probe-side
                # binary searches walk a table sized by CAPACITY, so a
                # 10%-live build would cost 10x the gathers it needs
                # (reference PagesIndex compacts build pages the same way)
                scap = bucket_capacity(max(int(summary[0]), 1))
                if scap < build.capacity:
                    from ..ops.jitcache import compact_jit
                    build = compact_jit(build, scap)
            prep = (self._prepare_join_build(build, node.right_keys,
                                             summary=summary,
                                             key_bounds=node.key_bounds)
                    if build is not None else None)
            if build is not None:
                from ..ops.join import lookup_form
                _note_join_strategy(
                    self.stats, node,
                    lookup_form(prep)
                    if node.build_unique else "expand",
                    node.distribution)
            # ONE build-side multiplicity readback replaces the per-probe-
            # batch match_count_max sync (each a host stall): the max key
            # multiplicity of the build bounds every probe batch's match
            # count, so the static expansion factor is known up front
            maxk_bound = (self._build_multiplicity(prep)
                          if build is not None and not node.build_unique
                          else None)
            for probe in probe_stream():
                if build is None:
                    if node.join_type == "inner":
                        continue
                    out = self._null_extend(probe, node)
                else:
                    if dyn:
                        probe = _apply_dynamic_bounds(probe, dyn)
                    if narrow is not None and narrow.looks_at(
                            probe.capacity):
                        probe = narrow(Batch(
                            probe.schema, probe.columns, semi_join_mask_jit(
                                probe, build, list(node.left_keys),
                                list(node.right_keys), False, False, prep)))
                    if residual_outer is not None:
                        for out in self._probe_outer_residual(
                                node, probe, build, payload,
                                payload_names, prep, residual_outer,
                                full_acc, maxk=maxk_bound):
                            yield compact(out)
                    else:
                        for out in self._probe_batches(
                                node, probe, build, payload,
                                payload_names, prep, maxk=maxk_bound):
                            if residual_fn is not None:
                                out = residual_fn(out)
                            yield compact(out)
                        if track_full:
                            m = build_match_mask_jit(
                                probe, build, list(node.left_keys),
                                list(node.right_keys), prep)
                            build_matched = (m if build_matched is None
                                             else build_matched | m)
                    continue
                if residual_fn is not None:
                    out = residual_fn(out)
                yield compact(out)
            if track_full:
                # FULL OUTER tail: build rows no probe row ever matched,
                # null-extended on the probe side (reference
                # LookupOuterOperator over the visited-positions bitmap)
                if full_acc is not None:
                    build_matched = full_acc["m"]
                yield compact(self._null_extend_build(
                    build, node, build_matched))
        finally:
            if probe_ex is not None:
                probe_ex.close()
            buf.close()

    def _dynamic_scan_target(self, probe: PlanNode):
        """(scan node, out-index -> scan-column mapping) when the probe
        chain maps columns straight to a scan through filters and identity
        projections; None otherwise."""
        mapping = {i: i for i in range(len(probe.fields))}
        node = probe
        while True:
            if node in self._ever_shared:
                return None  # replayed subtree feeds other consumers too
            if isinstance(node, FilterNode):
                node = node.child
                continue
            if isinstance(node, ProjectNode):
                new_map = {}
                for out_i, in_i in mapping.items():
                    e = node.exprs[in_i]
                    if isinstance(e, ir.InputRef):
                        new_map[out_i] = e.index
                mapping = new_map
                node = node.child
                continue
            break
        if not isinstance(node, TableScanNode) or not mapping:
            return None
        return node, mapping

    def _push_dynamic_bounds(self, probe: PlanNode,
                             dyn: List[Tuple[int, int, int]]) -> None:
        """Runtime scan pushdown: if the probe chain maps the join keys
        straight to scan columns (identity projections only), hand the
        build side's [lo, hi] to the scan so connectors prune on stats
        (reference sql/DynamicFilters.java:43 + the probe-side filter of
        LocalDynamicFiltersCollector; v319 collects build-side values and
        filters the probe scan the same way)."""
        target = self._dynamic_scan_target(probe)
        if target is None:
            return
        node, mapping = target
        extra = []
        for key_idx, lo, hi in dyn:
            scan_i = mapping.get(key_idx)
            if scan_i is None:
                continue
            extra.append((node.columns[scan_i], lo, hi))
        if extra:
            self.dynamic_pushdown.setdefault(node, []).extend(extra)

    def _partitioned_join(self, node: JoinNode, store, payload,
                          payload_names, residual_fn,
                          probe_batches: Optional[Iterator[Batch]] = None,
                          residual_outer=None) -> Iterator[Batch]:
        """Spilled-build probe: stage the probe side host-partitioned by
        the same key hash, then join partition-serially so only one build
        partition plus one probe chunk is device-resident at a time
        (reference GenericPartitioningSpiller.java probe protocol)."""
        from .spill import HostPartitionStore
        pstore: Optional[HostPartitionStore] = None
        # spilled builds join partition-serially over the sorted path:
        # a K-slot direct table per partition would multiply the very
        # memory pressure that forced the spill
        _note_join_strategy(
            self.stats, node,
            "sorted" if node.build_unique else "expand", "partitioned")
        if probe_batches is None:
            probe_batches = self.run(node.left)
        try:
            for probe in probe_batches:
                if pstore is None:
                    pstore = HostPartitionStore(probe.schema, store.n,
                                                pool=self.pool)
                pstore.add(probe, list(node.left_keys))
            if pstore is None:
                if node.join_type == "full":
                    # no probe rows at all: every build row is unmatched
                    for p in range(store.n):
                        bpart = store.partition_batch(p)
                        if bpart is not None:
                            yield self._null_extend_build(bpart, node, None)
                return
            for p in range(store.n):
                bpart = store.partition_batch(p)
                part_matched = None
                part_prep = None
                part_maxk = None
                for probe_p in pstore.partition_batches(
                        p, self.rows_per_batch):
                    if bpart is None:
                        if node.join_type in ("left", "full"):
                            yield self._null_extend(probe_p, node)
                        continue
                    if part_prep is None:
                        part_prep = self._prepare_join_build(
                            bpart, node.right_keys)
                        if not node.build_unique:
                            part_maxk = self._build_multiplicity(part_prep)
                    if residual_outer is not None:
                        # each probe row hashes to exactly one partition,
                        # so per-partition outer semantics compose to the
                        # global outer join
                        part_acc = ({"m": None}
                                    if node.join_type == "full" else None)
                        for out in self._probe_outer_residual(
                                node, probe_p, bpart, payload,
                                payload_names, part_prep, residual_outer,
                                part_acc, maxk=part_maxk):
                            yield out
                        if part_acc is not None \
                                and part_acc["m"] is not None:
                            part_matched = (
                                part_acc["m"] if part_matched is None
                                else part_matched | part_acc["m"])
                        continue
                    for out in self._probe_batches(node, probe_p, bpart,
                                                   payload, payload_names,
                                                   part_prep,
                                                   maxk=part_maxk):
                        yield residual_fn(out) if residual_fn is not None \
                            else out
                    if node.join_type == "full":
                        m = build_match_mask_jit(probe_p, bpart,
                                                 list(node.left_keys),
                                                 list(node.right_keys),
                                                 part_prep)
                        part_matched = (m if part_matched is None
                                        else part_matched | m)
                if node.join_type == "full" and bpart is not None:
                    yield self._null_extend_build(bpart, node,
                                                  part_matched)
        finally:
            if pstore is not None:
                pstore.close()

    #: per-kernel expansion cap: one skewed key would otherwise scale the
    #: expand_join output (probe_capacity x max_matches) without bound;
    #: past this the executor slices the build into bounded-multiplicity
    #: chunks via build_key_ranks
    SKEW_MATCH_LIMIT = 64

    #: largest (max-min+1) key span served by a direct-address lookup
    #: table (2^26 slots x 2 x i32 = 512MB of HBM); wider spans fall back
    #: to the composite binary search
    DIRECT_SPAN_LIMIT = 1 << 26

    def _build_summary(self, build: Batch, keys):
        """Host copy of the fused build reduction: [live_count,
        lo_0, hi_0, lo_1, hi_1, ...] over the given key columns (one
        readback; see ops/jitcache.py build_summary_jit)."""
        import numpy as np

        from ..ops.jitcache import build_summary_jit
        int_flags = tuple(isinstance(build.columns[k].type, _DYN_TYPES)
                          for k in keys)
        return np.asarray(device_sync(
            "build-summary",
            build_summary_jit(build, tuple(keys), int_flags)))

    @staticmethod
    def _summary_bounds(summary, out_keys):
        """[(out_key, lo, hi), ...] for the integer keys in a summary
        (non-integer keys carry the (0, -1) empty sentinel)."""
        out = []
        for i, pk in enumerate(out_keys):
            lo, hi = int(summary[1 + 2 * i]), int(summary[2 + 2 * i])
            if lo <= hi:
                out.append((pk, lo, hi))
        return out

    def _prepare_join_build(self, build: Batch, keys, summary=None,
                            key_bounds=(), unique: bool = False):
        """LookupSource choice (reference HashBuilderOperator's
        BigintGroupByHash-vs-MultiChannel split), stats-first:

        1. planner-promised ``key_bounds`` (JoinNode.key_bounds, any
           arity) build a mixed-radix composite direct-address table
           with PLAN-TIME-KNOWN capacity — stable executable shapes
           across batches and queries sharing the plan. The build batch
           is cross-checked against the promised bounds through the
           row-error channel (STATS_BOUND_VIOLATION — the dense-group
           contract: stats that lie fail the query, never corrupt it);
        2. a single integer key with a bounded MEASURED range gets the
           runtime direct table (bounds from the caller's fused build
           summary, no extra sync);
        3. anything else gets the sorted composite search.

        Direct tables answer a probe key in one gather (two for a run's
        length) independent of build size, where the sorted path pays
        O(log n) random gathers per probe lane — the dominant join cost
        on this hardware. A build of at most COMPARE_ALL_LIMIT lanes
        gets NO table, whatever the planner promised (the promise is
        still checked): the probe compares each lane with every key of
        the sorted layout and gathers nothing (ops/join._compare_all).
        ``unique``: the planner knows every key once (a residual semi
        join's summary): a direct table then addresses the build as it
        stands, unsorted (ops/join.build_in_order)."""
        from ..ops.join import COMPARE_ALL_LIMIT, direct_keyed_plan
        keys = tuple(keys)
        small = build.capacity <= COMPARE_ALL_LIMIT
        if key_bounds and bool_property(self.session, "join_dense_path",
                                        True):
            plan = direct_keyed_plan(tuple(key_bounds))
            if plan is not None:
                los, sizes, K = plan
                self.error_flags.append(key_bounds_violation_jit(
                    build, keys, tuple(key_bounds)))
                if not small:
                    return prepare_direct_keyed_jit(
                        build, keys, los, sizes, bucket_capacity(K),
                        unique)
        if small:
            return prepare_build_jit(build, keys)
        if len(keys) == 1 and isinstance(build.columns[keys[0]].type,
                                         _DYN_TYPES):
            if summary is None:
                summary = self._build_summary(build, keys)
            if int(summary[0]) > 0:
                lo, hi = int(summary[1]), int(summary[2])
                span = hi - lo + 1
                if 0 < span <= self.DIRECT_SPAN_LIMIT:
                    return prepare_direct_jit(
                        build, keys, lo, bucket_capacity(span), unique)
        return prepare_build_jit(build, keys)

    def _pallas_probe_on(self) -> bool:
        return bool_property(self.session, "join_pallas_probe", False)

    def _dispatch_lookup(self, probe: Batch, build: Batch, lkeys, rkeys,
                         payload, payload_names, jt: str, prepared):
        """Unique-build probe dispatch: the Pallas fused probe kernel
        when the ``join_pallas_probe`` session property (default off)
        and the backend/VMEM gate admit it, the XLA gather path
        otherwise. Nothing catches a kernel failure: with the property
        on, a kernel that does not lower fails the query with the
        compiler's message."""
        from ..ops import pallas_join as PJ
        pallas = self._pallas_probe_on() and PJ.supports_join(
            prepared, build, payload)
        _note_payload_form(probe.capacity, build.capacity, len(payload),
                           pallas)
        if pallas:
            return lookup_join_pallas_jit(
                probe, build, lkeys, rkeys, payload, payload_names,
                jt, prepared)
        return lookup_join_jit(probe, build, lkeys, rkeys, payload,
                               payload_names, jt, prepared)

    def _build_multiplicity(self, prepared) -> Optional[int]:
        """Host int of the build's max key multiplicity (one readback,
        amortized over every probe batch of the join) — or None when the
        build is skewed past SKEW_MATCH_LIMIT. The bound is only used to
        size expand_join when it is SMALL: for a skewed build, sizing
        every probe batch by the hottest key would push all batches into
        the chunked skew path (most probe batches never touch the hot
        key), so those fall back to the per-batch match_count_max sync."""
        from ..ops.jitcache import max_multiplicity_jit
        m = int(device_sync("build-multiplicity",
                            max_multiplicity_jit(prepared)))
        return m if m <= self.SKEW_MATCH_LIMIT else None

    def _probe_batches(self, node: JoinNode, probe: Batch, build: Batch,
                       payload, payload_names,
                       prepared=None, maxk=None) -> Iterator[Batch]:
        schema = _plan_schema(node)
        lkeys, rkeys = list(node.left_keys), list(node.right_keys)
        if prepared is None:
            prepared = prepare_build_jit(build, rkeys)
        # FULL OUTER probes like LEFT; the executor emits the
        # unmatched-build tail separately
        jt = "left" if node.join_type == "full" else node.join_type
        if node.build_unique:
            out = self._dispatch_lookup(probe, build, lkeys, rkeys,
                                        payload, payload_names, jt,
                                        prepared)
            yield Batch(schema, out.columns, out.row_mask)
            return
        if maxk is None:
            # skewed build (or standalone call): per-probe-batch count —
            # only batches that actually hit the hot key pay the chunked
            # skew loop below
            maxk = int(match_count_max_jit(probe, build, lkeys, rkeys,
                                           prepared))
        limit = self.SKEW_MATCH_LIMIT
        if maxk <= limit:
            k = bucket_capacity(max(maxk, 1), minimum=1)
            _note_payload_form(k * probe.capacity, build.capacity,
                               len(payload))
            out = expand_join_jit(
                probe, build, lkeys, rkeys, payload, payload_names, jt,
                k, prepared)
            yield Batch(schema, out.columns, out.row_mask)
            return
        # skew fallback: chunk the build by within-key occurrence rank so
        # each expand stays bounded. Ranks are dense from 0, so a probe
        # row with any match always matches in chunk 0 — chunk 0 keeps the
        # outer-join behavior, later chunks join inner.
        ranks = build_key_ranks_jit(build, rkeys, prepared)
        for c in range(0, maxk, limit):
            sub = Batch(build.schema, build.columns,
                        build.row_mask & (ranks >= c)
                        & (ranks < c + limit))
            _note_payload_form(limit * probe.capacity, sub.capacity,
                               len(payload))
            out = expand_join_jit(
                probe, sub, lkeys, rkeys, payload, payload_names,
                jt if c == 0 else "inner", limit, None)
            yield Batch(schema, out.columns, out.row_mask)

    def _probe_outer_residual(self, node: JoinNode, probe: Batch,
                              build: Batch, payload, payload_names,
                              prepared, residual_fn,
                              full_acc, maxk=None) -> Iterator[Batch]:
        """LEFT/FULL OUTER probe with a residual (join-filter) predicate:
        a probe row pairs with the build rows whose keys match AND whose
        residual passes; a probe row with no surviving match is
        reinstated null-extended (reference LookupJoinOperator +
        sql/gen/JoinFilterFunctionCompiler.java semantics: the ON filter
        gates matches, never drops probe rows). ``full_acc`` (FULL only)
        accumulates the build rows with at least one SURVIVING match for
        the unmatched-build tail.

        The residual runs only over matched lanes (row_mask = match), so
        its row-error channel fires exactly for rows the filter really
        evaluates — identical semantics on every executor."""
        from ..ops.jitcache import (expand_match_origins_jit,
                                    unique_match_build_mask_jit)
        schema = _plan_schema(node)
        lkeys, rkeys = list(node.left_keys), list(node.right_keys)
        npro = len(node.left.fields)

        def mark_full(mask):
            if full_acc is not None:
                full_acc["m"] = mask if full_acc["m"] is None \
                    else full_acc["m"] | mask

        if node.build_unique:
            out = self._dispatch_lookup(probe, build, lkeys, rkeys,
                                        payload, payload_names, "left",
                                        prepared)
            match = semi_join_mask_jit(probe, build, lkeys, rkeys,
                                       False, False, prepared)
            gated = residual_fn(Batch(schema, out.columns,
                                      probe.row_mask & match))
            survived = gated.row_mask
            cols = list(out.columns[:npro])
            for c in out.columns[npro:]:
                cols.append(Column(c.type, c.data,
                                   c.validity & survived, c.dictionary))
            if full_acc is not None:
                mark_full(unique_match_build_mask_jit(
                    probe, build, lkeys, rkeys, survived, prepared))
            yield Batch(schema, cols, probe.row_mask)
            return

        if maxk is None:
            maxk = int(match_count_max_jit(probe, build, lkeys, rkeys,
                                           prepared))
        limit = self.SKEW_MATCH_LIMIT
        if maxk <= limit:
            subs = [(build, bucket_capacity(max(maxk, 1), minimum=1),
                     prepared)]
        else:
            ranks = build_key_ranks_jit(build, rkeys, prepared)
            subs = [(Batch(build.schema, build.columns,
                           build.row_mask & (ranks >= c)
                           & (ranks < c + limit)), limit, None)
                    for c in range(0, maxk, limit)]
        has_survivor = None
        for sub, k, prep_c in subs:
            _note_payload_form(k * probe.capacity, sub.capacity,
                               len(payload))
            e = expand_join_jit(probe, sub, lkeys, rkeys, payload,
                                payload_names, "inner", k, prep_c)
            gated = residual_fn(Batch(schema, e.columns, e.row_mask))
            survived = gated.row_mask
            hs = jnp.any(survived.reshape(k, probe.capacity), axis=0)
            has_survivor = hs if has_survivor is None \
                else has_survivor | hs
            if full_acc is not None:
                orig, _ = expand_match_origins_jit(
                    probe, sub, lkeys, rkeys, k, prep_c)
                n = sub.capacity
                mark_full(jnp.zeros(n, dtype=bool).at[
                    jnp.where(survived, orig, n)].max(
                        survived, mode="drop"))
            yield Batch(schema, e.columns, survived)
        # reinstate probe rows with no surviving match, null-extended
        reinstated = self._null_extend(probe, node)
        yield Batch(schema, reinstated.columns,
                    probe.row_mask & ~(has_survivor
                                       if has_survivor is not None
                                       else jnp.zeros_like(
                                           probe.row_mask)))

    def _null_extend_build(self, build: Batch, node: JoinNode,
                           matched) -> Batch:
        """Unmatched build rows as output rows with NULL probe columns."""
        cap = build.capacity
        mask = build.row_mask
        if matched is not None:
            mask = mask & ~matched
        novalid = jnp.zeros(cap, dtype=bool)
        cols = []
        for f in node.left.fields:
            cols.append(Column(
                f.type, jnp.zeros(cap, dtype=f.type.storage_dtype),
                novalid, () if f.type.is_string else None))
        cols.extend(build.columns)
        return Batch(_plan_schema(node), cols, mask)

    def _null_extend(self, probe: Batch, node: JoinNode) -> Batch:
        cols = list(probe.columns)
        novalid = jnp.zeros_like(probe.row_mask)
        for f in node.fields[len(node.left.fields):]:
            cols.append(Column(
                f.type, jnp.zeros(probe.capacity, dtype=f.type.storage_dtype),
                novalid, () if f.type.is_string else None))
        return Batch(_plan_schema(node), cols, probe.row_mask)

    def _cross_join(self, node: JoinNode, build: Optional[Batch]
                    ) -> Iterator[Batch]:
        """Cross join where one side is tiny (scalar subqueries, VALUES)."""
        if build is None:
            return
        build = compact_jit(build, build.capacity)
        nb = build.host_count()
        if nb == 0:
            return
        for probe in self.run(node.left):
            cap = probe.capacity
            reps: List[Batch] = []
            for k in range(nb):
                cols = list(probe.columns)
                for c in build.columns:
                    val = c.data[k]
                    valid_k = c.validity[k]
                    cols.append(Column(
                        c.type, jnp.broadcast_to(val, (cap,)),
                        jnp.broadcast_to(valid_k, (cap,)) & probe.row_mask,
                        c.dictionary))
                reps.append(Batch(_plan_schema(node), cols, probe.row_mask))
            yield concat_batches(reps) if len(reps) > 1 else reps[0]

    def _SemiJoinNode(self, node: SemiJoinNode) -> Iterator[Batch]:
        skeys = list(node.source_keys)
        fkeys = list(node.filtering_keys)
        # how a residual is decided: on the ONE row a source row's keys
        # find where the planner knows the filtering side unique (a
        # summary by key, optimizer._summarize_semi_residuals; a primary
        # key): `keyed`; over the m:n matches otherwise: `expand`
        form = (None if node.residual is None
                else "keyed" if node.filtering_unique else "expand")
        # the filtering side's columns the residual reads
        res_payload = (() if form is None else _residual_payload(
            node.residual, len(node.source.fields))[0])
        summary = prep = packed = res_maxk = None
        # the filtering side of a residual semi join from its first
        # batch to the prepared layout, its launches and readbacks
        # included (they are the build's cost)
        with (TRACER.span("semi-build", form=form, rows_in=-1,
                          groups_out=-1) if form is not None
              else contextlib.nullcontext()) as span:
            build = self._drain(node.filtering)
            if build is not None:
                # the same cut _join_once gives its build, from the same
                # readback: what a HAVING left of a 2^24-lane state
                # (TPC-H Q18: ~60 keys) is probed as the bucket of its
                # live rows
                summary = self._build_summary(build, fkeys)
                scap = bucket_capacity(max(int(summary[0]), 1))
                if scap < build.capacity:
                    build = compact_jit(build, scap)
                prep = self._prepare_join_build(
                    build, fkeys, summary=summary,
                    key_bounds=node.key_bounds, unique=form == "keyed")
                from ..ops.join import is_direct_prepared, lookup_form
                _note_join_strategy(self.stats, node, lookup_form(prep),
                                    node.distribution, form)
                if form == "keyed":
                    packed = pack_sorted_payload_jit(
                        build, res_payload, prep, is_direct_prepared(prep))
                elif form == "expand":
                    res_maxk = self._build_multiplicity(prep)
                if span is not None:
                    # the live rows of the build, from the readback the
                    # build's cut made: the groups of a keyed summary,
                    # the rows an expansion pairs the source with
                    span.annotate(**{"groups_out" if form == "keyed"
                                     else "rows_in": int(summary[0])})
        for b in self.run(node.source):
            if build is None:
                if node.negated:
                    yield b
                else:
                    yield Batch(b.schema, b.columns,
                                jnp.zeros_like(b.row_mask))
                continue
            if form is None:
                mask = semi_join_mask_jit(b, build, skeys, fkeys,
                                          node.negated, node.null_aware,
                                          prep)
            elif form == "keyed":
                mask, err = _residual_program(form, node, b.schema)(
                    (b, build, prep, packed))
            else:
                maxk = res_maxk if res_maxk is not None else int(
                    match_count_max_jit(b, build, skeys, fkeys, prep))
                maxk = bucket_capacity(max(maxk, 1), minimum=1)
                _SEMI_EXPANDED_LANES.inc(b.capacity * maxk)
                _note_payload_form(b.capacity * maxk, build.capacity,
                                   len(res_payload))
                mask, err = _residual_program(form, node, b.schema, maxk)(
                    (b, build, prep))
            if form is not None and err is not None:
                self.error_flags.append(err)
            yield Batch(b.schema, b.columns, mask)


# -- counters on the selective-filter path (TPC-H Q6) -------------------------
# Down here, and their call sites rewritten line for line, because a
# line that MOVES in this file re-keys the Pallas kernels traced below
# its frames: `op_grouped_aggregate` then compiles cold for a quarter of
# an hour (PERF.md section 7, row 3).

#: `_compactor.maybe_compact`: batches whose liveness was read back,
#: batches shrunk, and their capacities before and after (equal where a
#: batch was left as it was): out over in is what the compaction saves
#: the kernels downstream
_COMPACT_CHECKED = REGISTRY.counter("compact_checked_total")
_COMPACT_APPLIED = REGISTRY.counter("compact_applied_total")
_COMPACT_LANES_IN = REGISTRY.counter("compact_lanes_in_total")
_COMPACT_LANES_OUT = REGISTRY.counter("compact_lanes_out_total")

#: the ungrouped branch of `_AggregationNode`: a partial state a batch,
#: and each time several states are stacked into one to be merged
_GLOBAL_PARTIALS = REGISTRY.counter("global_agg_partials_total")
_GLOBAL_MERGES = REGISTRY.counter("global_agg_merges_total")


def _note_compaction(lanes_in: int, lanes_out: int) -> None:
    _COMPACT_CHECKED.inc()
    _COMPACT_APPLIED.inc(lanes_out < lanes_in)
    _COMPACT_LANES_IN.inc(lanes_in)
    _COMPACT_LANES_OUT.inc(lanes_out)


def _global_partial(b: Batch, aggs) -> Batch:
    _GLOBAL_PARTIALS.inc()
    return global_aggregate(b, aggs, mode="partial")


def _global_states(parts: List[Batch]) -> Batch:
    """The partial states as one batch, for the merge or the final
    reduction."""
    if len(parts) == 1:
        return parts[0]
    _GLOBAL_MERGES.inc()
    return concat_batches(parts)
