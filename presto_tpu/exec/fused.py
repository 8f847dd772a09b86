"""Whole-pipeline fusion: one jitted program per join probe pipeline, and
one per scan batch under a small dense group-by (the aggregation sink).

The reference compiles each operator to bytecode but still moves data
between operators one Page at a time through the Driver loop (reference
operator/Driver.java:367-400). On this backend the equivalent
per-operator dispatch is far more expensive: every operator boundary is
a separate XLA executable whose outputs MATERIALIZE in HBM — a chain of
N unique-build dimension joins re-writes the full fact-table width N
times and pays N kernel-launch round trips per batch (the "~15 gather
passes" q27 diagnosis in docs/perf.md).

This module fuses a probe pipeline — a chain of unique-build lookup
joins, filters, and projections over one streaming source — into ONE
jitted function. XLA then keeps intermediate columns in registers/HBM
exactly once, dead columns are eliminated end-to-end, and a probe batch
pays one dispatch for the whole chain. The analogue in spirit of the
reference's ScanFilterAndProjectOperator fusion (reference
operator/ScanFilterAndProjectOperator.java:62), generalized to join
chains.

Fusion is semantics-preserving: each stage applies the SAME kernel the
standalone operator would (lookup_join / eval_expr), so results are
identical; only materialization boundaries change. The executor decides
WHAT to fuse (exec/local.py _try_fused_chain) and keeps the generic
per-operator path for everything else (skewed builds, residual filters,
outer tails, shared subtrees).

The aggregation sink (``agg_step``) is the join-probe fusion's twin on
the other end of a pipeline: a grouped aggregation whose grouping takes
the small dense path folds its input as ``step(state, batch) ->
(state', err)``, ONE program a scan batch. The step traces the
filter/project chain directly under the aggregation, the partial
group-by of the result and the merge of that partial into the running
state; the per-operator path launches each of these on its own and
merges every sixteenth partial through ``concat_batches``, which is
eager ``jnp`` code (some 870 launches a TPC-H Q1 at 58 batches). The
state has the dense code's slot layout at the fixed capacity
``bucket_capacity(K + 1)``, so under jit the concat before the merge
needs no dictionary remap; between launches it travels packed, one
array a dtype (``StateLayout``), because the host pays a launch by the
buffer. The executor selects it per operator from
what it can observe (exec/local.py _agg_step_chain, _agg_step_states):
a ``single`` or ``partial`` step, no drain or wide-state aggregate,
string or boolean keys whose ``dense_group_plan`` on the batch at hand
has ``scatter`` false (K <= 4096), ``task_concurrency`` 1, not the mesh
executor, no plan-template parameter in the chain, no shared interior
node. Everything else runs the per-operator path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..batch import Batch, Column, Schema, bucket_capacity
from ..expr import ir
from ..expr.compiler import Val, eval_expr, merge_err
from .. import types as T  # noqa: F401  (type objects live in stage fields)
from ..ops.aggregation import dense_group_plan, grouped_aggregate
from ..ops.join import lookup_join, semi_join_mask


@dataclasses.dataclass(frozen=True)
class JoinStage:
    """One unique-build lookup join. ``dyn_keys`` are probe-schema column
    indices with runtime [lo, hi] bounds from the build summary (inner
    joins only) — values arrive as traced scalars so changing bounds
    never recompiles. ``pallas`` routes this stage's probe through the
    fused Pallas ragged-gather kernel (ops/pallas_join) — the executor
    sets it only with the ``join_pallas_probe`` session property on
    (default off), for direct-address prepared builds within the VMEM
    budget; a kernel that fails to lower fails the query."""
    lkeys: Tuple[int, ...]
    rkeys: Tuple[int, ...]
    payload: Tuple[int, ...]
    names: Tuple[str, ...]
    join_type: str                        # inner | left
    out_fields: Tuple[Tuple[str, object], ...]
    dyn_keys: Tuple[int, ...] = ()
    pallas: bool = False


@dataclasses.dataclass(frozen=True)
class FilterStage:
    pred: ir.Expr


@dataclasses.dataclass(frozen=True)
class ProjectStage:
    exprs: Tuple[ir.Expr, ...]
    out_names: Tuple[str, ...]


def _vals(batch: Batch):
    inputs = [Val(c.data, c.validity, c.type, c.dictionary)
              for c in batch.columns]
    if not inputs:
        inputs = [Val(batch.row_mask, batch.row_mask, T.BOOLEAN)]
    return inputs


def _apply_stages(cur: Batch, stages, preps, builds, dyns, errs):
    """Apply stages in order over a traced batch; joins consume
    preps/builds/dyns positionally. Appends per-stage error scalars to
    ``errs``; returns the resulting batch."""
    ji = 0
    for st in stages:
        if isinstance(st, JoinStage):
            if st.dyn_keys:
                keep = cur.row_mask
                b = dyns[ji]
                for j, ki in enumerate(st.dyn_keys):
                    c = cur.columns[ki]
                    keep = keep & c.validity & (c.data >= b[j, 0]) \
                        & (c.data <= b[j, 1])
                cur = Batch(cur.schema, cur.columns, keep)
            if st.pallas:
                from ..ops.pallas_join import lookup_join_direct
                out = lookup_join_direct(cur, builds[ji], st.lkeys,
                                         st.rkeys, st.payload, st.names,
                                         st.join_type, preps[ji])
            else:
                out = lookup_join(cur, builds[ji], st.lkeys, st.rkeys,
                                  st.payload, st.names, st.join_type,
                                  prepared=preps[ji])
            cur = Batch(Schema(list(st.out_fields)), out.columns,
                        out.row_mask)
            ji += 1
        elif isinstance(st, FilterStage):
            p = eval_expr(st.pred, _vals(cur))
            keep = cur.row_mask & p.valid & p.data
            if p.err is not None:
                errs.append(jnp.max(jnp.where(cur.row_mask, p.err,
                                              jnp.int32(0))))
            cur = Batch(cur.schema, cur.columns, keep)
        else:  # ProjectStage
            outs = [eval_expr(e, _vals(cur)) for e in st.exprs]
            cols = [Column(o.type, o.data, o.valid & cur.row_mask,
                           o.dictionary) for o in outs]
            row_errs = merge_err(*[o.err for o in outs])
            if row_errs is not None:
                errs.append(jnp.max(jnp.where(cur.row_mask, row_errs,
                                              jnp.int32(0))))
            cur = Batch(Schema([(n, e.type) for n, e in
                                zip(st.out_names, st.exprs)]),
                        cols, cur.row_mask)
    return cur


def _merge_errs(errs) -> Optional[jnp.ndarray]:
    if not errs:
        return None
    err = errs[0]
    for e in errs[1:]:
        err = jnp.maximum(err, e)
    return err


@functools.lru_cache(maxsize=None)
def fused_pipeline(stages: Tuple[object, ...]):
    """jitted fn(probe, preps, builds, dyns) -> (Batch, err_or_None).

    ``preps``/``builds``/``dyns`` are tuples with one entry per JoinStage
    (bottom-up order); ``dyns[i]`` is an [n_bounds, 2] i64 array aligned
    with that stage's dyn_keys. Capacity/schema specialization happens
    inside jax.jit (pytree structure + shapes are the dispatch key), so
    one cache entry serves every batch size bucket of the chain.
    """

    def run(probe: Batch, preps, builds, dyns):
        errs = []
        cur = _apply_stages(probe, stages, preps, builds, dyns, errs)
        return cur, _merge_errs(errs)

    # timed_entry: the fused chain is an executable like any jitcache
    # entry — compile time, invocations, and (under a profile context)
    # device time land in obs.profiler.EXECUTABLES, attributed to the
    # join node whose frame dispatches the chain
    from ..ops.jitcache import timed_entry
    return timed_entry("fused_pipeline", run, stages)


@functools.lru_cache(maxsize=None)
def fused_prefilter(stages: Tuple[object, ...],
                    pre_keys: Tuple[int, ...],
                    semi_keys: Optional[Tuple[Tuple[int, ...],
                                              Tuple[int, ...]]]):
    """jitted fn(probe, pre_bounds, semi_build, semi_prep)
    -> (Batch, err_or_None, live_count).

    The selectivity-first head of a fused join chain: ALL the chain's
    hoistable dynamic-filter key bounds (``pre_keys`` index the SOURCE
    schema; ``pre_bounds`` is the aligned [m, 2] i64 traced array) are
    evaluated on the raw source batch, then the source-side
    filter/project stages run, then — when the first join is inner —
    its key-membership mask (``semi_keys`` = (lkeys, rkeys)) gates the
    lanes WITHOUT gathering any payload. Payload gathers happen in the
    tail pipeline, after the executor compacts the surviving lanes — so
    a selective first join no longer gathers its build columns for all
    2^20 lanes per batch.

    ``live_count`` is a TRACED scalar (no readback here): the executor
    stacks a window of counts and syncs them in one RTT
    (exec/local.py:_run_fused_chain), amortizing the per-batch
    compaction liveness readback."""

    def run(probe: Batch, pre_bounds, semi_build, semi_prep):
        keep = probe.row_mask
        for j, ki in enumerate(pre_keys):
            c = probe.columns[ki]
            keep = keep & c.validity & (c.data >= pre_bounds[j, 0]) \
                & (c.data <= pre_bounds[j, 1])
        cur = Batch(probe.schema, probe.columns, keep)
        errs = []
        cur = _apply_stages(cur, stages, (), (), (), errs)
        if semi_keys is not None:
            lkeys, rkeys = semi_keys
            m = semi_join_mask(cur, semi_build, list(lkeys), list(rkeys),
                               negated=False, null_aware=False,
                               prepared=semi_prep)
            cur = Batch(cur.schema, cur.columns, cur.row_mask & m)
        count = jnp.sum(cur.row_mask.astype(jnp.int32))
        return cur, _merge_errs(errs), count

    from ..ops.jitcache import timed_entry
    return timed_entry("fused_prefilter", run,
                       (stages, pre_keys, semi_keys))


def _partial_of(batch: Batch, stages, group, aggs, key_bounds, errs):
    """The aggregation's input (``stages`` over ``batch``) and its
    partial group-by: what the filter, projection and partial launches
    of the per-operator path compute, in one trace."""
    cur = _apply_stages(batch, stages, (), (), (), errs)
    return cur, grouped_aggregate(cur, group, aggs, mode="partial",
                                  key_bounds=key_bounds)


def _concat_rows(a: Batch, b: Batch) -> Batch:
    """Row-wise concat of two traced state batches of ONE layout (the
    dictionaries are static and equal, so codes mean the same on both
    sides: the remap ``concat_batches`` does between vocabularies has
    nothing to do here)."""
    cols = []
    for x, y in zip(a.columns, b.columns):
        if x.dictionary != y.dictionary:
            raise ValueError("agg_step: state and partial dictionaries "
                             "differ (the executor flushes first)")
        cols.append(Column(x.type, jnp.concatenate([x.data, y.data]),
                           jnp.concatenate([x.validity, y.validity]),
                           x.dictionary))
    return Batch(a.schema, cols,
                 jnp.concatenate([a.row_mask, b.row_mask]))


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """The running state of the aggregation sink between two launches:
    the state batch's leaves (every one a ``[capacity]`` array) stacked
    into ONE 2-D array a dtype. A launch costs the host by the buffer
    (TPU v5e, PERF.md section 5: 0.19 ms and some 0.036 ms for each
    array in or out), and a state batch is two arrays a column: Q1's 19
    columns were 78 of a step's 93 buffers. ``treedef`` is the state
    batch's pytree structure (schema and dictionaries: its layout);
    ``groups`` the leaf indices stacked into each array, by dtype."""
    treedef: object
    capacity: int
    groups: Tuple[Tuple[str, Tuple[int, ...]], ...]

    def pack(self, state: Batch):
        leaves = jax.tree_util.tree_leaves(state)
        return tuple(jnp.stack([leaves[i] for i in idx])
                     for _, idx in self.groups)

    def unpack(self, packed) -> Batch:
        leaves = [None] * self.treedef.num_leaves
        for arr, (_, idx) in zip(packed, self.groups):
            for row, i in enumerate(idx):
                leaves[i] = arr[row]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


@functools.lru_cache(maxsize=256)
def agg_step(stages: Tuple[object, ...], group: Tuple[int, ...],
             aggs: Tuple[object, ...], key_bounds, layout: StateLayout):
    """jitted fn(state, batch) -> (state', err_or_None): the aggregation
    sink's program, ``op_agg_step``. ``stages`` are the Filter/Project
    stages under the aggregation (bottom-up, possibly none); ``state``
    is a partial-state batch in the dense slot layout, packed by
    ``layout`` (start from :func:`agg_step_start`'s empty one, end with
    :func:`agg_step_finish`) and comes back at its own capacity.
    ``batch`` is a scan batch, which the scan cache keeps: it is never
    donated. ``err`` merges the stages' row errors and the dense path's
    key-bounds violation."""
    from ..ops.jitcache import _bounds_violation_factory, timed_entry
    key_idx = tuple(range(len(group)))
    violation = (_bounds_violation_factory(group, key_bounds)
                 if key_bounds is not None else None)

    def run(packed, batch: Batch):
        errs = []
        cur, part = _partial_of(batch, stages, group, aggs, key_bounds,
                                errs)
        if violation is not None:
            errs.append(violation(cur))
        merged = grouped_aggregate(
            _concat_rows(layout.unpack(packed), part), key_idx, aggs,
            mode="merge", output_capacity=layout.capacity,
            key_bounds=key_bounds)
        return layout.pack(merged), _merge_errs(errs)

    return timed_entry("agg_step", run,
                       (stages, group, aggs, key_bounds, layout))


@functools.lru_cache(maxsize=256)
def agg_step_finish(layout: StateLayout):
    """jitted fn(state) -> Batch: the packed state as the partial-state
    batch the merge buffer takes (``op_agg_step_finish``; one launch a
    state, so one a query)."""
    from ..ops.jitcache import timed_entry
    return timed_entry("agg_step_finish", layout.unpack, layout)


@functools.lru_cache(maxsize=256)
def agg_step_start(stages: Tuple[object, ...], group: Tuple[int, ...],
                   aggs: Tuple[object, ...], key_bounds, treedef, avals):
    """(layout, empty packed state) for scan batches of one signature
    (``treedef``: schema and dictionaries; ``avals``: each leaf's shape
    and dtype), or None where the grouping of such a batch does not
    take the small dense path. Host-only: one abstract trace a
    signature, kept across queries with the state it gives (all dead
    rows, never written: the step does not donate it)."""
    batch = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, d) for s, d in avals])
    cur, part = jax.eval_shape(
        lambda b: _partial_of(b, stages, group, aggs, key_bounds, []),
        batch)
    plan = dense_group_plan(cur, group, cur.capacity, key_bounds)
    if plan is None or plan.scatter:
        return None
    cap = bucket_capacity(plan.K + 1)
    leaves, state_def = jax.tree_util.tree_flatten(part)
    if any(x.ndim != 1 for x in leaves):
        return None
    by_dtype = {}
    for i, x in enumerate(leaves):
        by_dtype.setdefault(str(x.dtype), []).append(i)
    layout = StateLayout(state_def, cap, tuple(
        (d, tuple(idx)) for d, idx in sorted(by_dtype.items())))
    empty = tuple(jnp.asarray(np.zeros((len(idx), cap), dtype=d))
                  for d, idx in layout.groups)
    return layout, empty
