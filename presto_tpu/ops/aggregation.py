"""Aggregation kernels: sort + segment-reduce group-by.

The TPU-native replacement for Presto's hash aggregation stack (reference
presto-main/.../operator/HashAggregationOperator.java:48,
MultiChannelGroupByHash.java, aggregation/builder/
InMemoryHashAggregationBuilder.java): instead of an open-addressing hash
table over channels, we sort rows by their group keys (lexicographic
``lax.sort``), detect segment boundaries, assign dense group ids by prefix
sum, and run ``jax.ops.segment_*`` reductions — everything static-shape and
branch-free on the VPU. NULL is a group key value like any other (SQL GROUP
BY semantics), encoded as a leading null-rank sort operand.

Two-phase execution mirrors Presto's PARTIAL/FINAL split (reference
AggregationNode.Step): partial emits state columns (sum+count, min+count...),
final re-aggregates states after an exchange. States are ordinary columns, so
the exchange layer needs no special serialization.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .prefix import prefix_sum
# imported EAGERLY so its module-level device constants (SIGN64, MASK32)
# are created outside any jit trace: a first import inside a traced
# kernel leaks tracer-scoped constants and fails the compile
# (UnexpectedTracerError seen on decimal aggregations whose first use
# was inside grouped_aggregate's jit)
from . import int128 as _int128  # noqa: F401
from .. import types as T
from ..batch import (Batch, Column, Schema, bucket_capacity, compress_lanes,
                     compress_moves, live_indices, per_lane, shift_lanes)
from ..types import Type

_VARIANCE_FNS = ("var_samp", "var_pop", "stddev_samp",
                 "stddev_pop")
_SUPPORTED = ("sum", "count", "count_star", "min", "max", "avg",
              "var_samp", "var_pop", "stddev_samp", "stddev_pop",
              "bool_and", "bool_or", "approx_percentile",
              "approx_distinct")
#: aggregates whose GROUPED form drains the input into one exact
#: 'single'-mode pass (reference computes these with QuantileDigest
#: sketches — state/DigestAndPercentileState.java). The GLOBAL numeric
#: form instead carries bounded mergeable histogram state through
#: partial -> exchange -> final like every other aggregate
#: (ops/sketch.py qd_*); only grouped and string-input forms drain,
#: because a dense per-group tile would be O(groups x bins) and
#: dictionary ranks are batch-local (not mergeable across shards).
DRAIN_FNS = ("approx_percentile",)


def has_drain_agg(aggs) -> bool:
    return any(a.fn in DRAIN_FNS for a in aggs)


def percentile_drains(aggs, input_types, grouped: bool) -> bool:
    """True when approx_percentile aggregates must run as an exact
    drain (see DRAIN_FNS): grouped aggregations and string inputs.
    ``input_types`` is the child schema's type list."""
    drains = [a for a in aggs if a.fn in DRAIN_FNS]
    if not drains:
        return False
    if grouped:
        return True
    # accepts AggSpec (.input) and planner PlanAgg (.arg) alike
    return any(
        input_types[a.input if hasattr(a, "input") else a.arg].is_string
        for a in drains)


#: largest fused key-domain the broadcast-compare dense reducers handle
#: ([rows, K] masked reduce); past this the scatter reducers take over
_DENSE_GROUP_LIMIT = 4096

#: largest fused key-domain of the stats-bounded dense SCATTER group-by
#: (one i32 scatter per digit over K slots — ~85-110M updates/s on v5e vs
#: ~8M/s for the 64-bit path and an 82s compile for the 3-operand
#: lax.sort it replaces); past this the mostly-empty slot table stops
#: paying for itself and the sort-segment path wins. Shared with the
#: planner's rewrite gate (optimizer._attach_group_bounds).
DENSE_SCATTER_LIMIT = 1 << 21


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: fn over an input column (None for count(*))."""

    fn: str
    input: Optional[int]          # column index in the input batch
    output_type: Type
    name: str = ""                # output column name
    # mask channel: rows where this boolean column is false don't feed
    # this aggregate (reference AggregationNode.Aggregation mask — the
    # MarkDistinct lowering of DISTINCT aggregates)
    mask: Optional[int] = None
    # static scalar parameter (approx_percentile's p)
    param: Optional[float] = None

    def __post_init__(self):
        assert self.fn in _SUPPORTED, self.fn

    # state layout produced by partial mode / consumed by final mode
    def state_types(self) -> List[Tuple[str, Type]]:
        base = self.name or self.fn
        if self.fn == "approx_percentile":
            # fixed-size log-linear histogram: the bounded mergeable
            # state the reference ships between partial and final steps
            # (state/DigestAndPercentileState.java); only the GLOBAL
            # numeric form uses it (grouped/string forms drain — see
            # DRAIN_FNS)
            from .sketch import QD_BINS
            return [(f"{base}$qdig", T.QdigestStateType(QD_BINS))]
        if self.fn == "approx_distinct":
            # fixed-size HLL register vector: the bounded mergeable state
            # the reference ships between partial and final steps
            # (state/HyperLogLogState.java); param carries the max
            # standard error
            from .sketch import hll_m
            return [(f"{base}$hll", T.HllStateType(hll_m(self.param)))]
        if self.fn in ("count", "count_star"):
            return [(f"{base}$cnt", T.BIGINT)]
        if self.fn == "avg":
            return [(f"{base}$sum", self._sum_type()), (f"{base}$cnt", T.BIGINT)]
        if self.fn in _VARIANCE_FNS:
            # central moments (mean, m2, count), not sum/sum-of-squares:
            # sumsq - sum^2/n cancels catastrophically for large-mean
            # low-variance data (reference
            # aggregation/state/CentralMomentsState.java stores central
            # moments for the same reason)
            return [(f"{base}$mean", T.DOUBLE), (f"{base}$m2", T.DOUBLE),
                    (f"{base}$cnt", T.BIGINT)]
        if self.fn in ("bool_and", "bool_or"):
            return [(f"{base}$val", T.INTEGER), (f"{base}$cnt", T.BIGINT)]
        return [(f"{base}$val", self._sum_type() if self.fn == "sum" else self.output_type),
                (f"{base}$cnt", T.BIGINT)]

    def _sum_type(self) -> Type:
        if isinstance(self.output_type, T.DecimalType):
            # decimal sums/avgs accumulate in decimal(38, s) two-limb
            # state like the reference (DecimalSumAggregation Int128
            # state; ops/int128.py digit-plane exact sums)
            return T.DecimalType(38, self.output_type.scale)
        return self.output_type


def mark_distinct_flags(batch: Batch,
                        cols: Sequence[int]) -> jnp.ndarray:
    """True at the first live occurrence of each distinct tuple of
    ``cols`` (reference operator/MarkDistinctOperator.java +
    MarkDistinctHash — hash-set membership replaced by sort + boundary +
    scatter-back, the branch-free device shape). Dead rows are False."""
    ops: List[jnp.ndarray] = [
        jnp.where(batch.row_mask, 0, 1).astype(jnp.int32)]
    for ci in cols:
        c = batch.columns[ci]
        data = c.data
        ops.append(jnp.where(c.validity, 0, 1).astype(jnp.int32))
        if getattr(data, "ndim", 1) == 2:
            from . import int128 as I
            ops.append(jnp.where(c.validity, I.hi(data),
                                 jnp.zeros_like(I.hi(data))))
            ops.append(jnp.where(c.validity, I.lo(data),
                                 jnp.zeros_like(I.lo(data))))
            continue
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
        ops.append(jnp.where(c.validity, data, jnp.zeros_like(data)))
    idx = jnp.arange(batch.capacity, dtype=jnp.int64)
    out = jax.lax.sort(ops + [idx], num_keys=len(ops), is_stable=True)
    s_live = out[0] == 0
    s_idx = out[-1]
    diff = jnp.zeros_like(s_live)
    for op in out[1:len(ops)]:
        diff = diff | (op != jnp.roll(op, 1))
    first = jnp.zeros_like(s_live).at[0].set(True)
    boundary = s_live & (diff | first)
    return jnp.zeros(batch.capacity, dtype=bool).at[s_idx].set(boundary)


#: the rank operand of a dead row; a live row's rank holds a bit a key,
#: set where that key is NULL (the first key's bit the highest)
_DEAD_RANK = 1 << 30


def _group_key_ops(batch: Batch,
                   group_indices: Sequence[int]) -> List[jnp.ndarray]:
    """Sort operands for GROUP BY keys: [rank, then per key its
    null-neutralized data]. ONE int32 rank carries what orders rows
    before their key data does: dead rows last, and the pattern of NULL
    keys (every operand more costs XLA's TPU sort compile seconds;
    grouping needs equal tuples adjacent, not any particular order of
    the groups). Shared by every kernel whose output rows must align
    positionally across separate sorts of the same batch
    (grouped_aggregate and the percentile drain), and the order the
    merge network of :func:`merge_states` keeps."""
    assert len(group_indices) < 30
    rank = jnp.where(batch.row_mask, 0, _DEAD_RANK).astype(jnp.int32)
    key_ops: List[jnp.ndarray] = [rank]
    for j, gi in enumerate(group_indices):
        c = batch.columns[gi]
        data = c.data
        null_bit = 1 << (len(group_indices) - 1 - j)
        rank = rank | jnp.where(c.validity | ~batch.row_mask, 0,
                                null_bit).astype(jnp.int32)
        if getattr(data, "ndim", 1) == 2:
            # long-decimal limb pairs: lexicographic (hi, unsigned lo)
            # is value order (ops/int128.py sortable_lo)
            from . import int128 as I
            key_ops.append(jnp.where(c.validity, I.hi(data),
                                     jnp.zeros_like(I.hi(data))))
            key_ops.append(jnp.where(c.validity, I.sortable_lo(data),
                                     jnp.zeros_like(I.lo(data))))
            continue
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
        # neutralize NULL rows' data so stale values can't split NULL groups
        key_ops.append(jnp.where(c.validity, data, jnp.zeros_like(data)))
    key_ops[0] = rank
    return key_ops


def _boundary_groups(s_keys, s_mask):
    """Boundary/group-id/start-index machinery over sorted key operands."""
    diff = jnp.zeros_like(s_mask)
    for op in s_keys:
        diff = diff | (op != jnp.roll(op, 1))
    first = jnp.zeros_like(s_mask).at[0].set(True)
    boundary = s_mask & (diff | first)
    group_id = jnp.maximum(prefix_sum(boundary.astype(jnp.int32)) - 1, 0)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    return boundary, group_id, num_groups


def _group_sort(batch: Batch, group_indices: Sequence[int],
                order_violation: Optional[list] = None):
    """Sort rows by group keys; return (key_operands, permuted batch arrays).

    Returns (sorted_cols, sorted_validity, sorted_mask, boundary, group_id,
    num_groups) where boundary marks the first live row of each group.

    Only the key operands plus a row index enter ``lax.sort``; payload
    columns are gathered by the resulting permutation. TPU variadic-sort
    compile time grows superlinearly with operand count (measured on v5e:
    ~215s cold for a 10-operand sort vs ~20s for keys+iota), so carrying
    the whole batch through the comparator is never worth it.
    """
    key_ops = _group_key_ops(batch, group_indices)
    rows = ([c.data for c in batch.columns],
            [c.validity for c in batch.columns], batch.row_mask)

    def squeezed():
        # dead lanes among the live ones (a filter's mask over a table
        # clustered by the keys: the late lines of TPC-H Q21) leave
        # through the compress network, the live rows to the front in
        # their order: log2(lanes) elementwise passes a column, where
        # the sort below costs a gather a column (~11 ns a lane)
        moves, count = compress_moves(batch.row_mask)
        live = jnp.arange(batch.capacity) < count

        def front(a):
            a = compress_lanes(moves, a)
            return jnp.where(per_lane(live, a), a, jnp.zeros_like(a))
        ops = [front(k) for k in key_ops]
        ops[0] = jnp.where(live, ops[0], _DEAD_RANK).astype(jnp.int32)
        data, valid, _ = jax.tree_util.tree_map(front, rows)
        return ops, (data, valid, live)
    key_ops, rows = jax.lax.cond(
        jnp.any(batch.row_mask[1:] & ~batch.row_mask[:-1]),
        squeezed, lambda: (key_ops, rows))

    def by_sort():
        idx = jnp.arange(batch.capacity, dtype=jnp.int32)
        # the row index is the LAST KEY: every row's tuple differs, so
        # the unstable sort is deterministic (compiled for a described
        # v5e at 2^20 rows: stable 124 s, unstable 61 s; PR 33)
        out = jax.lax.sort(key_ops + [idx], num_keys=len(key_ops) + 1,
                           is_stable=False)
        return list(out[:-1]), jax.tree_util.tree_map(
            lambda a: jnp.take(a, out[-1], axis=0), rows)

    # A batch whose rows stand in the keys' order already (a fact table
    # clustered by its key, a probe side that kept its order: lineitem
    # by l_orderkey in TPC-H Q3 and Q18) is grouped as it stands: no
    # sort, and no gather a column behind it (~11 ns a lane a column on
    # the v5e). Observed a batch, on the device; both branches compile.
    before = [shift_lanes(k, -1) for k in key_ops]
    ordered = jnp.all((_lex_greater(key_ops, before)
                       | _lex_equal(key_ops, before))[1:])
    if order_violation is not None:
        # the planner promises the order (the scan's table is clustered
        # by the keys: AggregationNode.ordered_input): the sort's branch
        # is not compiled (two thirds of this program's compile seconds
        # at 2^20 lanes), and a batch out of order fails the query
        # through the row-error channel, as a key outside its promised
        # bounds does
        from ..errors import STATS_BOUND_VIOLATION
        order_violation.append(jnp.where(
            ordered, jnp.int32(0), jnp.int32(STATS_BOUND_VIOLATION)))
        s_keys, (s_data, s_valid, s_mask) = key_ops, rows
    else:
        s_keys, (s_data, s_valid, s_mask) = jax.lax.cond(
            ordered, lambda: (key_ops, rows), by_sort)
    boundary, group_id, num_groups = _boundary_groups(s_keys, s_mask)
    return s_data, s_valid, s_mask, boundary, group_id, num_groups


def _wide_state_aggs(aggs: Sequence["AggSpec"]) -> bool:
    """Aggregates whose states need the sort path's leading row dim
    (HLL register tiles, decimal(38) limb pairs)."""
    return any(a.fn == "approx_distinct" for a in aggs) or any(
        getattr(st, "storage_width", None)
        for a in aggs if a.fn not in DRAIN_FNS
        for _, st in a.state_types())


def dense_path_selected(batch: "Batch", group_indices: Sequence[int],
                        aggs: Sequence["AggSpec"],
                        output_capacity: Optional[int] = None,
                        key_bounds=None) -> bool:
    """Host-only mirror of grouped_aggregate's kernel dispatch: True when
    this batch/grouping takes the dense composite-code path (broadcast or
    scatter), False when it sorts. The executor reports it (obs metric +
    EXPLAIN ANALYZE) without tracing anything."""
    if has_drain_agg(aggs) or _wide_state_aggs(aggs):
        return False
    cap = output_capacity or batch.capacity
    return dense_group_plan(batch, group_indices, cap,
                            key_bounds) is not None


@dataclasses.dataclass(frozen=True)
class DenseGroupPlan:
    """Host-static plan for the composite dense group code: one
    mixed-radix component per key (component 0 = NULL). ``los[i]`` is the
    integer key's stats-derived lower bound (None for dictionary/boolean
    keys, whose domain comes from the data itself); ``scatter`` selects
    the segment-scatter reducers over the [rows, K] broadcast reduce."""

    sizes: Tuple[int, ...]
    los: Tuple[Optional[int], ...]
    K: int
    scatter: bool


def dense_group_plan(batch: Batch, group_indices: Sequence[int],
                     cap: int,
                     key_bounds: Optional[Sequence[
                         Optional[Tuple[int, int]]]] = None
                     ) -> Optional[DenseGroupPlan]:
    """Dense-path dispatch rule (host-only — reads column aux data, no
    device math, so the executor can also call it to report which kernel
    a grouping takes). A key contributes a component when its domain is
    host-known: dictionary-coded strings (|vocab|), booleans, or integer
    keys with stats-derived [lo, hi] bounds from the planner
    (AggregationNode.key_bounds — the reference BigintGroupByHash
    dense-array mode generalized to mixed-radix composite keys). Returns
    None when any domain is unknown or the product overflows the limit —
    the sort-segment path then runs unchanged."""
    sizes: List[int] = []
    los: List[Optional[int]] = []
    bounded = False
    for j, gi in enumerate(group_indices):
        c = batch.columns[gi]
        kb = key_bounds[j] if key_bounds else None
        if c.type.is_string and c.dictionary is not None:
            sizes.append(len(c.dictionary) + 1)
            los.append(None)
        elif c.data.dtype == jnp.bool_:
            sizes.append(3)
            los.append(None)
        elif (kb is not None and getattr(c.data, "ndim", 1) == 1
                and jnp.issubdtype(c.data.dtype, jnp.integer)):
            lo, hi = int(kb[0]), int(kb[1])
            if hi < lo:
                return None
            sizes.append(hi - lo + 2)
            los.append(lo)
            bounded = True
        else:
            return None
    K = 1
    for s in sizes:
        K *= s
    limit = min(cap, DENSE_SCATTER_LIMIT if bounded else _DENSE_GROUP_LIMIT)
    if not 0 < K <= limit:
        return None
    return DenseGroupPlan(tuple(sizes), tuple(los), K,
                          scatter=bounded or K > _DENSE_GROUP_LIMIT)


def _dense_group_code(batch: Batch, group_indices: Sequence[int],
                      plan: DenseGroupPlan) -> jnp.ndarray:
    """Fused dense group slot: slot = mixed-radix(key components),
    component 0 = NULL. Group ids come straight from the data, so
    aggregation is a single segment-reduce pass with trivial compile
    time — no comparator, no permutation. A live key outside its stats
    bound CLAMPS into the domain (the slot table must stay in-bounds);
    the executor independently raises STATS_BOUND_VIOLATION for such
    rows through the row-error channel, so a misgrouped result never
    escapes the query."""
    code = jnp.zeros(batch.capacity, dtype=jnp.int32)
    for gi, size, lo in zip(group_indices, plan.sizes, plan.los):
        c = batch.columns[gi]
        if lo is None:
            comp = jnp.where(c.validity, c.data.astype(jnp.int32) + 1, 0)
        else:
            shifted = jnp.clip(c.data.astype(jnp.int64) - lo + 1, 1,
                               size - 1).astype(jnp.int32)
            comp = jnp.where(c.validity, shifted, 0)
        code = code * size + comp
    return code


def _dense_key_columns(batch: Batch, group_indices: Sequence[int],
                       plan: DenseGroupPlan, cap: int,
                       out_mask: jnp.ndarray) -> List[Column]:
    """Decode slot indices 0..K-1 back into key columns (static mixed-radix
    decode — becomes constants under jit), padded to ``cap``."""
    K = plan.K
    slots = np.arange(K, dtype=np.int64)
    comps: List[np.ndarray] = []
    for size in reversed(list(plan.sizes)):
        comps.append(slots % size)
        slots = slots // size
    comps.reverse()
    key_cols = []
    for gi, comp, lo in zip(group_indices, comps, plan.los):
        c = batch.columns[gi]
        valid = jnp.pad(jnp.asarray(comp > 0), (0, cap - K)) & out_mask
        if lo is not None:
            data = jnp.pad(jnp.asarray(
                lo + np.maximum(comp - 1, 0)).astype(c.data.dtype),
                (0, cap - K))
        elif c.data.dtype == jnp.bool_:
            data = jnp.pad(jnp.asarray(comp == 2), (0, cap - K))
        else:
            data = jnp.pad(
                jnp.asarray(np.maximum(comp - 1, 0)).astype(c.data.dtype),
                (0, cap - K))
        key_cols.append(Column(c.type, data, valid, c.dictionary))
    return key_cols


class _SegReducers:
    """Group reductions over the sorted runs of a group sort: ``gid``
    the dense group id a row, ``boundary`` the first row of each run,
    ``live`` the rows that count; results hold group g at index g, in
    ``cap`` lanes.

    A sum is a prefix sum over the rows and, a run, the difference of
    its last row's and of the row before its first: both picked out by
    the compress network (``batch.compress_moves``), the runs in their
    order, so no scatter and no gather (the 64-bit scatter runs ~8M
    rows/s on the v5e, a gather ~11 ns a lane; an int64 wraps and its
    differences are exact; f64 prefix differences round differently
    than per-group scatter order, which SQL sum(double) permits). A min
    or a max is a scan within the runs and the same pick of each run's
    last row (a 64-bit segment scatter took 0.13 s a 2^20-lane batch a
    column); a register tile's (HLL) stays a segment scatter."""

    def __init__(self, group_id: jnp.ndarray, cap: int,
                 boundary: jnp.ndarray, live: jnp.ndarray):
        self.gid, self.cap = group_id, cap
        self.first, self.num_groups = compress_moves(boundary)
        # a run's last row: the next is another run's first, or dead
        self.last, _ = compress_moves(
            live & (shift_lanes(boundary, 1) | ~shift_lanes(live, 1)))

    def _fit(self, x):
        n = x.shape[0]
        if self.cap <= n:
            return x[:self.cap]
        return jnp.pad(x, [(0, self.cap - n)] + [(0, 0)] * (x.ndim - 1))

    def front(self, x):
        """``x`` at each run's first row, group g at index g."""
        return self._fit(compress_lanes(self.first, x))

    def count(self, valid):
        return self.sum(valid.astype(jnp.int32)).astype(jnp.int64)

    def sum(self, x):
        if getattr(x, "ndim", 0) != 1:
            return jax.ops.segment_sum(x, self.gid, num_segments=self.cap)
        csum = prefix_sum(x)
        return self._fit(compress_lanes(self.last, csum)
                         - compress_lanes(self.first, shift_lanes(csum, -1)))

    def _over_runs(self, x, fn):
        """``fn`` folded over each run of ``x`` (the rows stand run by
        run), a run's result where its last row stood: a scan that
        doubles its reach a pass and never crosses a run's first row
        (log2 of the lanes elementwise passes), then the compress
        network, as for a sum."""
        run = self.gid + 1              # zeros move in: no run's number
        for k in range((x.shape[0] - 1).bit_length()):
            same = shift_lanes(run, -(1 << k)) == run
            x = jnp.where(same, fn(x, shift_lanes(x, -(1 << k))), x)
        return self._fit(compress_lanes(self.last, x))

    def min(self, x):
        if getattr(x, "ndim", 0) != 1:
            return jax.ops.segment_min(x, self.gid, num_segments=self.cap)
        return self._over_runs(x, jnp.minimum)

    def max(self, x):
        if getattr(x, "ndim", 0) != 1:
            return jax.ops.segment_max(x, self.gid, num_segments=self.cap)
        return self._over_runs(x, jnp.maximum)

    def hll(self, valid, hashed, m):
        """HLL register update: one segment_max over flattened
        (group, bucket) slots (ops/sketch.py)."""
        from .sketch import hll_update
        return hll_update(self.gid, valid, hashed, self.cap, m)

    def gather(self, per_group):
        return per_group[self.gid]


class _DenseReducers:
    """Group reductions for a small static slot count K via a broadcast
    compare + axis-0 reduce (no scatter: a TPU scatter-add over 8M rows
    costs ~0.5s while the [N, K] masked reduce is memory-bound — measured
    ~8x faster end-to-end on v5e)."""

    def __init__(self, code: jnp.ndarray, K: int):
        self.code, self.cap = code, K
        self._match = None

    def _m(self):
        if self._match is None:
            self._match = (self.code[:, None]
                           == jnp.arange(self.cap,
                                         dtype=self.code.dtype)[None, :])
        return self._match

    def count(self, valid):
        # accumulate in i32 (counts < 2^31 within one batch): this
        # broadcast reduce is memory-bound and i64 doubles its traffic
        return self.sum(valid.astype(jnp.int32)).astype(jnp.int64)

    def sum(self, x):
        return jnp.sum(jnp.where(self._m(), x[:, None],
                                 jnp.zeros((), x.dtype)), axis=0)

    def min(self, x):
        return jnp.min(jnp.where(self._m(), x[:, None],
                                 _max_sentinel(x.dtype)), axis=0)

    def max(self, x):
        return jnp.max(jnp.where(self._m(), x[:, None],
                                 _min_sentinel(x.dtype)), axis=0)

    def gather(self, per_group):
        return per_group[self.code]


class _ScatterReducers:
    """Group reductions over a dense i32 composite key code via
    ``segment_*`` scatters — the bounded-domain no-sort path for key
    spaces too wide for the [rows, K] broadcast reduce above. The group
    id needs no sort and no boundary pass (it IS the key), so the whole
    aggregation is a handful of scatters: counts are one i32 scatter,
    exact 64-bit sums go through the i32 digit scatters of
    ops/scatter_agg.py (the f64/i64 scatter is the ~14x cliff on this
    chip), and f64 sums scatter directly in f64 (SQL sum(double)
    tolerates the reduction order; the magnitude is still exact f64
    adds). Signed inputs scatter positive and negative magnitudes
    separately — the digit split needs non-negative values."""

    def __init__(self, code: jnp.ndarray, cap: int, n_rows: int):
        self.gid, self.cap, self.n_rows = code, cap, n_rows

    def count(self, valid):
        ones = jnp.where(valid, jnp.int32(1), jnp.int32(0))
        c = jax.ops.segment_sum(ones, self.gid, num_segments=self.cap)
        return c.astype(jnp.int64)

    def sum(self, x):
        if x.dtype == jnp.int64 and getattr(x, "ndim", 1) == 1:
            from .scatter_agg import segment_sum_exact
            pos = segment_sum_exact(jnp.maximum(x, 0), self.gid,
                                    self.cap, self.n_rows, value_bits=62)
            neg = segment_sum_exact(jnp.maximum(-x, 0), self.gid,
                                    self.cap, self.n_rows, value_bits=62)
            return pos - neg
        return jax.ops.segment_sum(x, self.gid, num_segments=self.cap)

    def min(self, x):
        return jax.ops.segment_min(x, self.gid, num_segments=self.cap)

    def max(self, x):
        return jax.ops.segment_max(x, self.gid, num_segments=self.cap)

    def hll(self, valid, hashed, m):
        from .sketch import hll_update
        return hll_update(self.gid, valid, hashed, self.cap, m)

    def gather(self, per_group):
        return per_group[self.gid]


def _segment_aggs(
    aggs: Sequence[AggSpec],
    col_data: Sequence[jnp.ndarray],
    col_valid: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    red,
    from_states: bool,
    col_dicts: Optional[Sequence[Optional[Tuple[str, ...]]]] = None,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Per-aggregate (value_arrays...) segment reductions.

    Returns, per agg, a list of (data, counts-ish) arrays matching its state
    layout when ``from_states`` is False, or merged states when True.
    """
    results = []
    state_cursor = 0
    for agg in aggs:
        if from_states:
            # inputs are state columns in layout order
            n_state = len(agg.state_types())
            s_cols = list(range(state_cursor, state_cursor + n_state))
            state_cursor += n_state
            if agg.fn == "approx_percentile":
                raise NotImplementedError(
                    "grouped approx_percentile is drain-only "
                    "(see percentile_drains)")
            if agg.fn == "approx_distinct":
                # HLL merge = per-bucket max of register rows [n, m];
                # 0 is the register identity so dead rows drop out
                regs_in = col_data[s_cols[0]]
                live2 = mask[:, None]
                merged = red.max(jnp.where(live2, regs_in,
                                           jnp.zeros_like(regs_in)))
                results.append((jnp.maximum(merged, 0),))
                continue
            if agg.fn in ("count", "count_star"):
                cnt_in = jnp.where(mask, col_data[s_cols[0]], 0)
                cnt = red.sum(cnt_in)
                results.append((cnt,))
                continue
            if agg.fn in _VARIANCE_FNS:
                # merge partial (mean, m2, n) states: Chan's parallel
                # combination generalized to k partials —
                # M2 = sum(m2_i + n_i * (mean_i - mean)^2)
                m_in = col_data[s_cols[0]]
                m2_in = col_data[s_cols[1]]
                cnt_raw = col_data[s_cols[2]]
                live = mask & (cnt_raw > 0)
                nw = jnp.where(live, cnt_raw, 0)
                cnt = red.sum(nw)
                nf = nw.astype(jnp.float64)
                n = jnp.maximum(cnt, 1).astype(jnp.float64)
                wsum = red.sum(nf * jnp.where(live, m_in, 0.0))
                mean = wsum / n
                dev = m_in - red.gather(mean)
                # corrected combine: (sum n_i*dev_i)^2/n cancels the
                # weighted-sum rounding error in the computed mean
                wdev = red.sum(jnp.where(live, nf * dev, 0.0))
                m2 = red.sum(jnp.where(live, m2_in + nf * dev * dev, 0.0)) - wdev * wdev / n
                results.append((mean + wdev / n, m2, cnt))
                continue
            stype = agg.state_types()[0][1]
            if isinstance(stype, T.DecimalType) and stype.is_long:
                from . import int128 as I
                val_in = col_data[s_cols[0]]        # [n, 2] limbs
                cnt_raw = col_data[s_cols[1]]
                cnt = red.sum(jnp.where(mask, cnt_raw, 0))
                live = mask & (cnt_raw > 0)
                if agg.fn in ("sum", "avg"):
                    val = _checked_sum128(val_in, live, red.sum)
                else:
                    val = _minmax128(val_in, live, red, agg.fn)
                results.append((val, cnt))
                continue
            val_in = col_data[s_cols[0]]
            cnt_raw = col_data[s_cols[1]]
            cnt_in = jnp.where(mask, cnt_raw, 0)
            cnt = red.sum(cnt_in)
            live = mask & (cnt_raw > 0)
            vocab = col_dicts[s_cols[0]] if col_dicts else None
            if vocab is not None and agg.fn in ("min", "max"):
                val = _rank_reduce(val_in, live, red, vocab, agg.fn)
            elif agg.fn in ("sum", "avg"):
                contrib = jnp.where(live, val_in, jnp.zeros_like(val_in))
                val = red.sum(contrib)
            elif agg.fn in ("bool_and", "min"):
                sent = _max_sentinel(val_in.dtype)
                contrib = jnp.where(live, val_in, sent)
                val = red.min(contrib)
            else:  # max / bool_or
                sent = _min_sentinel(val_in.dtype)
                contrib = jnp.where(live, val_in, sent)
                val = red.max(contrib)
            results.append((val, cnt))
            continue
        # raw-input mode
        if agg.fn == "count_star":
            cnt = red.count(mask)
            results.append((cnt,))
            continue
        data = col_data[agg.input]
        valid = col_valid[agg.input] & mask
        if agg.mask is not None:
            valid = valid & col_data[agg.mask].astype(bool)
        if agg.fn == "approx_distinct":
            from .sketch import hashed_column, hll_m
            vocab = col_dicts[agg.input] if col_dicts else None
            hashed = hashed_column(data, vocab)
            results.append((red.hll(valid, hashed, hll_m(agg.param)),))
            continue
        cnt = red.count(valid)
        if agg.fn == "count":
            results.append((cnt,))
            continue
        if agg.fn in _VARIANCE_FNS:
            # corrected two-pass central moments: mean first, then squared
            # deviations with the (sum dev)^2/n correction term that
            # cancels the first-pass sum's rounding error — stable for
            # any magnitude
            x = data.astype(jnp.float64)
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            s = red.sum(jnp.where(valid, x, 0.0))
            mean = s / n
            dev = jnp.where(valid, x - red.gather(mean), 0.0)
            s1 = red.sum(dev)
            m2 = red.sum(dev * dev) - s1 * s1 / n
            results.append((mean + s1 / n, m2, cnt))
            continue
        if agg.fn in ("bool_and", "bool_or"):
            x = data.astype(jnp.int32)
            if agg.fn == "bool_and":
                contrib = jnp.where(valid, x, jnp.int32(1))
                val = red.min(contrib)
            else:
                contrib = jnp.where(valid, x, jnp.int32(0))
                val = red.max(contrib)
            results.append((val, cnt))
            continue
        vocab = col_dicts[agg.input] if col_dicts else None
        if vocab is not None and agg.fn in ("min", "max"):
            val = _rank_reduce(data, valid, red, vocab, agg.fn)
            results.append((val, cnt))
            continue
        acc_t = agg.state_types()[0][1]
        if isinstance(acc_t, T.DecimalType) and acc_t.is_long:
            # decimal(38) accumulation: short inputs sign-extend to
            # limbs, long inputs pass through; sums are exact digit-
            # plane scatters (ops/int128.py)
            from . import int128 as I
            x = data if data.ndim == 2 else I.from_i64(data)
            if agg.fn in ("sum", "avg"):
                val = _checked_sum128(x, valid, red.sum)
            else:
                val = _minmax128(x, valid, red, agg.fn)
            results.append((val, cnt))
            continue
        acc_dtype = acc_t.storage_dtype
        x = data.astype(acc_dtype)
        if agg.fn in ("sum", "avg"):
            if isinstance(acc_t, T.DecimalType) and isinstance(agg.output_type, T.DecimalType):
                pass  # same scale accumulate
            contrib = jnp.where(valid, x, jnp.zeros_like(x))
            val = red.sum(contrib)
        elif agg.fn == "min":
            contrib = jnp.where(valid, x, _max_sentinel(acc_dtype))
            val = red.min(contrib)
        else:
            contrib = jnp.where(valid, x, _min_sentinel(acc_dtype))
            val = red.max(contrib)
        results.append((val, cnt))
    return results


def _checked_sum128(x: jnp.ndarray, live: jnp.ndarray, red_sum) -> jnp.ndarray:
    """Exact 128-bit sum of limb tiles [n, 2] with overflow poisoning:
    groups whose true sum exceeds 38 digits (or that merge an already
    poisoned partial) yield the OVERFLOW_SENTINEL, which raises
    NUMERIC_VALUE_OUT_OF_RANGE when the value is decoded (the deferred
    analogue of the reference DecimalSumAggregation throw)."""
    from . import int128 as I
    planes = jnp.where(live[:, None], I.digit_sum_tiles(x), 0)
    val, ovf = I.from_digit_sum_tiles_checked(red_sum(planes))
    ovf = ovf | ~I.fits_decimal(val, 38)
    poisoned = red_sum((live & I.is_overflow_sentinel(x))
                       .astype(jnp.int32)) > 0
    sent = jnp.broadcast_to(jnp.asarray(I.OVERFLOW_SENTINEL), val.shape)
    return jnp.where((ovf | poisoned)[..., None], sent, val)


class _GlobalReducer:
    """Single-group reducer with the _SegReducers surface (min/max/sum
    collapse all rows; gather broadcasts), so grouped and global code
    paths share the int128 kernels below."""

    def sum(self, x):
        return jnp.sum(x, axis=0)

    def min(self, x):
        return jnp.min(x, axis=0)

    def max(self, x):
        return jnp.max(x, axis=0)

    def gather(self, per_group):
        return per_group


def _minmax128(x: jnp.ndarray, live: jnp.ndarray, red, fn: str) -> jnp.ndarray:
    """Grouped min/max over int128 limb tiles [n, 2]: lexicographic
    (hi, unsigned lo) in two segment reductions — reduce hi, then lo
    among rows tied at the winning hi."""
    from . import int128 as I
    h = I.hi(x)
    l = I.sortable_lo(x)
    op = red.min if fn == "min" else red.max
    sent_h = _max_sentinel(h.dtype) if fn == "min" else _min_sentinel(h.dtype)
    mh = op(jnp.where(live, h, sent_h))
    tie = live & (h == red.gather(mh))
    ml = op(jnp.where(tie, l, sent_h))
    return I.pack(mh, ml ^ I.SIGN64)


def _finalize_dec128(agg: AggSpec, val: jnp.ndarray, cnt: jnp.ndarray):
    """Shared long-decimal finalize: avg divide (poisoned past the
    2^31-row divisor bound and through overflowed sums), short-output
    narrowing. ``val`` is [..., 2] limbs."""
    from . import int128 as I
    out_t = agg.output_type
    short_out = isinstance(out_t, T.DecimalType) and not out_t.is_long
    if agg.fn == "avg":
        den = jnp.clip(cnt, 1, 1 << 31)
        q = I.div_round_half_up(val, den)
        # poisoned sums stay poisoned; counts past the short-division
        # bound poison too rather than divide by a clipped count
        bad = I.is_overflow_sentinel(val) | (cnt > (1 << 31))
        q = I.where(bad, jnp.broadcast_to(jnp.asarray(I.OVERFLOW_SENTINEL),
                                          q.shape), q)
        return (I.lo(q) if short_out else q)
    return (I.lo(val) if short_out else val)


def _rank_reduce(codes: jnp.ndarray, live: jnp.ndarray, red,
                 vocab: Tuple[str, ...], fn: str) -> jnp.ndarray:
    """min/max over dictionary codes in LEXICOGRAPHIC order: map codes to
    ranks, segment-reduce, map the winning rank back to a code (reference
    MinMaxHelpers over VARCHAR; codes are appearance-ordered, not
    sorted)."""
    from .sort import rank_codes, unrank_table
    ranks = rank_codes(codes, vocab).astype(jnp.int64)
    if fn == "min":
        r = red.min(jnp.where(live, ranks, jnp.iinfo(jnp.int64).max))
    else:
        r = red.max(jnp.where(live, ranks, -1))
    table = unrank_table(vocab)
    safe = jnp.clip(r, 0, table.shape[0] - 1)
    return jnp.take(table, safe, axis=0)


def _rank_reduce_scalar(codes: jnp.ndarray, live: jnp.ndarray,
                        vocab: Tuple[str, ...], fn: str) -> jnp.ndarray:
    """Global (single-group) variant of _rank_reduce."""
    from .sort import rank_codes, unrank_table
    ranks = rank_codes(codes, vocab).astype(jnp.int64)
    if fn == "min":
        r = jnp.min(jnp.where(live, ranks, jnp.iinfo(jnp.int64).max))
    else:
        r = jnp.max(jnp.where(live, ranks, -1))
    table = unrank_table(vocab)
    return jnp.take(table, jnp.clip(r, 0, table.shape[0] - 1))


def _max_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype=dtype)


def _min_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype=dtype)


def _variance_out(agg, mean, m2, cnt):
    """(mean, m2, count) central-moment state -> variance/stddev."""
    del mean
    n = jnp.maximum(cnt, 1).astype(jnp.float64)
    pop = agg.fn in ("var_pop", "stddev_pop")
    den = n if pop else jnp.maximum(n - 1.0, 1.0)
    var = jnp.maximum(m2, 0.0) / den
    out = jnp.sqrt(var) if agg.fn.startswith("stddev") else var
    valid = (cnt > 0) if pop else (cnt > 1)
    return out, valid


def _finalize(agg: AggSpec, parts: Tuple[jnp.ndarray, ...]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """state -> (output data, output validity)."""
    if agg.fn in ("count", "count_star"):
        return parts[0], jnp.ones_like(parts[0], dtype=bool)
    if agg.fn == "approx_distinct":
        from .sketch import hll_estimate
        regs = parts[0]
        return hll_estimate(regs), jnp.ones(regs.shape[:-1], dtype=bool)
    if agg.fn in _VARIANCE_FNS:
        return _variance_out(agg, *parts)
    val, cnt = parts
    valid = cnt > 0
    if agg.fn in ("bool_and", "bool_or"):
        return val > 0, valid
    if val.ndim == 2:
        # long-decimal limb state (sum/avg/min/max over decimals)
        return _finalize_dec128(agg, val, cnt), valid
    if agg.fn == "avg":
        if isinstance(agg.output_type, T.DecimalType):
            den = jnp.maximum(cnt, 1)
            q = val / den
            out = (jnp.sign(q) * jnp.floor(jnp.abs(val) / den + 0.5)).astype(jnp.int64)
            return out, valid
        den = jnp.maximum(cnt, 1).astype(val.dtype)
        return val / den, valid
    out = val.astype(agg.output_type.storage_dtype)
    return out, valid


def _percentile_input(batch: Batch, input_idx: int, mask_idx):
    """(valid, sort_value, unrank) for a percentile input column: dictionary
    codes map through lexicographic ranks so value order is string order
    (codes are appearance-ordered); unrank maps the winner back to a code."""
    c = batch.columns[input_idx]
    if getattr(c.data, "ndim", 1) == 2:
        raise NotImplementedError(
            "grouped approx_percentile over decimal(>18) is not "
            "supported (cast to decimal(18,s) or double)")
    valid = c.validity & batch.row_mask
    if mask_idx is not None:
        valid = valid & batch.columns[mask_idx].data.astype(bool)
    vdata = c.data
    unrank = None
    if c.dictionary is not None:
        from .sort import rank_codes, unrank_table
        vdata = rank_codes(vdata, c.dictionary).astype(jnp.int64)
        unrank = unrank_table(c.dictionary)
    elif vdata.dtype == jnp.bool_:
        vdata = vdata.astype(jnp.int32)
    return valid, vdata, unrank


def _select_ks(aggs: Sequence[AggSpec], nvalid: jnp.ndarray):
    """Per-agg nearest-rank index (0-based) within the valid run."""
    ks = []
    for agg in aggs:
        p = float(agg.param if agg.param is not None else 0.5)
        ks.append(jnp.clip(jnp.ceil(p * nvalid).astype(jnp.int64) - 1, 0,
                           jnp.maximum(nvalid - 1, 0)))
    return ks


def _grouped_percentiles(batch: Batch, group_indices: Sequence[int],
                         aggs: Sequence[AggSpec], cap: int):
    """Nearest-rank percentiles per group for aggregates sharing one
    (input, mask): ONE segmented sort by (group keys, value), k selections.
    Valid values sort first within each group, so the k-th smallest valid
    value sits at (group start + k). Group order comes from the shared
    _group_key_ops operands, so outputs align positionally with
    grouped_aggregate's rows."""
    valid, vdata, unrank = _percentile_input(batch, aggs[0].input,
                                             aggs[0].mask)
    key_ops = _group_key_ops(batch, group_indices)
    val_null = jnp.where(valid, 0, 1).astype(jnp.int32)
    vneutral = jnp.where(valid, vdata, jnp.zeros_like(vdata))
    out = jax.lax.sort(key_ops + [val_null, vneutral],
                       num_keys=len(key_ops) + 2, is_stable=False)
    s_live = out[0] < _DEAD_RANK
    s_keys = out[:len(key_ops)]
    s_vnull, s_vals = out[-2], out[-1]
    boundary, group_id, num_groups = _boundary_groups(s_keys, s_live)
    nvalid = jax.ops.segment_sum(
        (s_live & (s_vnull == 0)).astype(jnp.int64), group_id,
        num_segments=cap)
    bidx = live_indices(boundary, cap)[0]
    out_mask = jnp.arange(cap) < num_groups
    results = []
    for k in _select_ks(aggs, nvalid):
        sel = jnp.clip(bidx + k, 0, batch.capacity - 1)
        data = jnp.take(s_vals, sel, axis=0)
        if unrank is not None:
            data = jnp.take(unrank, jnp.clip(data, 0, unrank.shape[0] - 1),
                            axis=0)
        results.append((data, (nvalid > 0) & out_mask))
    return results


def _global_percentiles(batch: Batch, aggs: Sequence[AggSpec]):
    """Single-group nearest-rank percentiles (one sort, k selections)."""
    valid, vdata, unrank = _percentile_input(batch, aggs[0].input,
                                             aggs[0].mask)
    val_null = jnp.where(valid, 0, 1).astype(jnp.int32)
    vneutral = jnp.where(valid, vdata, jnp.zeros_like(vdata))
    _, s_vals = jax.lax.sort([val_null, vneutral], num_keys=2,
                             is_stable=False)
    n = jnp.sum(valid.astype(jnp.int64))
    results = []
    for k in _select_ks(aggs, n):
        data = jnp.take(s_vals, k)
        if unrank is not None:
            data = jnp.take(unrank, jnp.clip(data, 0, unrank.shape[0] - 1))
        results.append((data, n > 0))
    return results


def _drain_groups(aggs):
    """Drain aggs grouped by shared (input, mask) -> one sort per group."""
    groups: dict = {}
    for agg in aggs:
        if agg.fn in DRAIN_FNS:
            groups.setdefault((agg.input, agg.mask), []).append(agg)
    return groups


def _with_drain_aggs(batch: Batch, group_indices, aggs, mode,
                     output_capacity) -> Batch:
    """grouped_aggregate with approx_percentile columns spliced in."""
    if mode != "single":
        raise NotImplementedError(
            "approx_percentile requires single-step aggregation "
            "(the planner routes such plans through a drain)")
    cap = output_capacity or batch.capacity
    regular = [a for a in aggs if a.fn not in DRAIN_FNS]
    # percentile drains align with the regular aggregates POSITIONALLY
    # (both orderings come from the shared _group_key_ops sort), so the
    # dense no-sort path must not reorder groups here
    base = grouped_aggregate(batch, group_indices, regular, "single",
                             output_capacity, allow_dense=False)
    computed = {}
    for shared in _drain_groups(aggs).values():
        for agg, res in zip(shared, _grouped_percentiles(
                batch, group_indices, shared, cap)):
            computed[id(agg)] = res
    nk = len(group_indices)
    out_cols = list(base.columns[:nk])
    out_fields = list(zip(base.schema.names[:nk], base.schema.types[:nk]))
    ri = nk
    for agg in aggs:
        if agg.fn in DRAIN_FNS:
            data, valid = computed[id(agg)]
            out_fields.append((agg.name or agg.fn, agg.output_type))
            out_cols.append(Column(
                agg.output_type,
                data.astype(agg.output_type.storage_dtype), valid,
                batch.columns[agg.input].dictionary
                if agg.output_type.is_string else None))
        else:
            out_cols.append(base.columns[ri])
            out_fields.append((base.schema.names[ri], base.schema.types[ri]))
            ri += 1
    return Batch(Schema(out_fields), out_cols, base.row_mask)


def grouped_aggregate(
    batch: Batch,
    group_indices: Sequence[int],
    aggs: Sequence[AggSpec],
    mode: str = "single",
    output_capacity: Optional[int] = None,
    allow_dense: bool = True,
    key_bounds: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    order_violation: Optional[list] = None,
) -> Batch:
    """GROUP BY aggregation. mode: 'single' | 'partial' | 'final' | 'merge'.

    In 'final' and 'merge' modes the input batch layout must be
    [group key columns..., state columns in agg order...] — i.e. the output
    layout of 'partial' mode (possibly concatenated/exchanged in between).
    'merge' re-combines state rows sharing a key but keeps the state layout
    (Presto's intermediate combine step), enabling hierarchical merging.

    ``key_bounds`` (one Optional[(lo, hi)] per group key, from
    AggregationNode.key_bounds) lets integer keys join the dense
    composite-code path; see dense_group_plan.

    ``order_violation``: a list, given where the planner promises that
    every batch's live rows stand in the keys' order: the sort path then
    groups them as they stand, compiles no sort, and appends the device
    scalar that says whether the promise held (an error code or 0).
    """
    assert mode in ("single", "partial", "final", "merge")
    if has_drain_agg(aggs):
        return _with_drain_aggs(batch, group_indices, aggs, mode,
                                output_capacity)
    cap = output_capacity or batch.capacity
    from_states = mode in ("final", "merge")
    n_keys = len(group_indices)
    if _wide_state_aggs(aggs):
        # wide states (HLL register tiles, decimal(38) limb pairs) need
        # the sort path whose segment ops keep a leading row dim; the
        # dense broadcast-compare reducer would materialize [rows, K, w]
        allow_dense = False
    plan = (dense_group_plan(batch, group_indices, cap, key_bounds)
            if allow_dense else None)
    if plan is not None:
        # no-sort fast path: group id straight from the key data. The
        # output shrinks to the key domain's bucket when the caller left
        # capacity open — a 2^20-row batch grouping into a 10^5-slot
        # domain must not ship 2^20-capacity state columns downstream.
        K = plan.K
        if output_capacity is None:
            cap = min(cap, bucket_capacity(K + 1))
        code = _dense_group_code(batch, group_indices, plan)
        mask = batch.row_mask
        gid = jnp.where(mask, code, K)       # dead rows -> overflow slot
        red = (_ScatterReducers(gid, K + 1, batch.capacity)
               if plan.scatter else _DenseReducers(gid, K + 1))
        occ = red.count(mask)[:K] > 0
        out_mask = jnp.pad(occ, (0, cap - K))
        key_cols = _dense_key_columns(batch, group_indices, plan, cap,
                                      out_mask)
        in_cols = batch.columns[n_keys:] if from_states else batch.columns
        raw = _segment_aggs(
            aggs, [c.data for c in in_cols], [c.validity for c in in_cols],
            mask, red, from_states=from_states,
            col_dicts=[c.dictionary for c in in_cols])
        seg = [tuple(jnp.pad(arr[:K], [(0, cap - K)] + [(0, 0)] * (
            getattr(arr, "ndim", 1) - 1)) for arr in parts)
               for parts in raw]
    else:
        s_data, s_valid, s_mask, boundary, group_id, num_groups = \
            _group_sort(batch, group_indices, order_violation)

        red = _SegReducers(group_id, cap, boundary, s_mask)
        out_mask = jnp.arange(cap) < num_groups
        # group key output: the first row of each run
        key_cols = []
        for gi in group_indices:
            c = batch.columns[gi]
            key_cols.append(Column(
                c.type,
                jax.tree_util.tree_map(red.front, s_data[gi]),
                red.front(s_valid[gi]) & out_mask,
                c.dictionary,
            ))
        if from_states:
            state_data = s_data[n_keys:]
            state_dicts = [c.dictionary for c in batch.columns[n_keys:]]
            seg = _segment_aggs(aggs, state_data, s_valid[n_keys:], s_mask,
                                red, from_states=True,
                                col_dicts=state_dicts)
        else:
            seg = _segment_aggs(aggs, s_data, s_valid, s_mask,
                                red, from_states=False,
                                col_dicts=[c.dictionary
                                           for c in batch.columns])

    return _assemble(batch, group_indices, aggs, mode, key_cols, seg,
                     out_mask)


def _assemble(batch: Batch, group_indices: Sequence[int],
              aggs: Sequence[AggSpec], mode: str, key_cols: List[Column],
              seg, out_mask: jnp.ndarray) -> Batch:
    """The output batch of a grouping over ``batch``: the key columns,
    then the states (``partial``/``merge``) or the finalized values of
    the per-aggregate reductions ``seg``."""
    from_states = mode in ("final", "merge")

    def value_dict(agg: AggSpec):
        """Dictionary for a string-valued min/max output/state column."""
        if agg.fn not in ("min", "max") or agg.input is None:
            return None
        if from_states:
            cursor = 0
            for a in aggs:
                if a is agg:
                    break
                cursor += len(a.state_types())
            return batch.columns[len(group_indices) + cursor].dictionary
        return batch.columns[agg.input].dictionary

    out_cols: List[Column] = list(key_cols)
    out_fields: List[Tuple[str, Type]] = [
        (batch.schema.names[gi], batch.schema.types[gi]) for gi in group_indices
    ]
    if mode in ("partial", "merge"):
        for agg, parts in zip(aggs, seg):
            vd = value_dict(agg)
            for (fname, ftype), arr in zip(agg.state_types(), parts):
                out_fields.append((fname, ftype))
                out_cols.append(Column(
                    ftype, arr.astype(ftype.storage_dtype), out_mask,
                    vd if ftype.is_string else None))
    else:
        for agg, parts in zip(aggs, seg):
            data, valid = _finalize(agg, parts)
            name = agg.name or agg.fn
            out_fields.append((name, agg.output_type))
            out_cols.append(Column(
                agg.output_type, data.astype(agg.output_type.storage_dtype),
                valid & out_mask,
                value_dict(agg) if agg.output_type.is_string else None))
    return Batch(Schema(out_fields), out_cols, out_mask)


# -- states of unique keys: merge without a sort, finish without one ----------
#
# What a sort-path ``partial`` or ``merge`` gives back is NORMALIZED: its
# live rows first, ascending by the operands of :func:`_group_key_ops`,
# every key once. Two such states merge in a bitonic merge network
# (log2 of the lanes elementwise passes, the state columns riding along)
# where ``lax.sort`` would sort them as if they were in no order and a
# gather a column would follow (~11 ns a lane a column on the v5e); two
# whose key ranges do not overlap are their concatenation, and skip the
# network too (2^23 lanes a side, three columns, on the v5e: 9 ms where
# the network takes 239, ``tools/merge_probe.py``); and any state of
# unique keys finishes lane by lane.

def merge_network_ok(batch: Batch, n_keys: int,
                     aggs: Sequence[AggSpec]) -> bool:
    """Host-only: can :func:`merge_states` take states laid out as
    ``batch``? Its comparator is the signed integer order ``lax.sort``
    gives integer operands, so every key is one integer (or boolean, or
    dictionary-coded) column; a DOUBLE key (NaN, the zeros) or a
    decimal(38) limb pair sorts through ``grouped_aggregate``."""
    if has_drain_agg(aggs) or n_keys >= 30:
        return False
    for c in batch.columns[:n_keys]:
        if getattr(c.data, "ndim", 1) != 1 or not (
                c.data.dtype == jnp.bool_
                or jnp.issubdtype(c.data.dtype, jnp.integer)):
            return False
    return True


def _lex_greater(a: Sequence[jnp.ndarray],
                 b: Sequence[jnp.ndarray]) -> jnp.ndarray:
    gt = jnp.zeros(a[0].shape, bool)
    eq = jnp.ones(a[0].shape, bool)
    for x, y in zip(a, b):
        gt = gt | (eq & (x > y))
        eq = eq & (x == y)
    return gt


def _lex_equal(a: Sequence[jnp.ndarray],
               b: Sequence[jnp.ndarray]) -> jnp.ndarray:
    eq = jnp.ones(a[0].shape, bool)
    for x, y in zip(a, b):
        eq = eq & (x == y)
    return eq


def _bitonic_merge(keys: List[jnp.ndarray],
                   payload: List[jnp.ndarray]):
    """``keys`` (lexicographic, each half of the lanes ascending) and
    the ``payload`` that rides along, all lanes ascending. The second
    half is reversed, which makes the lanes bitonic, and log2(lanes)
    half-cleaners follow: lane i and lane i ^ s keep the smaller and
    the larger tuple. Flat elementwise passes over shifted copies, as
    ``batch.live_indices``: no sort, no gather, no small minor
    dimension for the TPU to pad."""
    n = keys[0].shape[0]
    ops = [jnp.concatenate([x[:n // 2], jnp.flip(x[n // 2:], axis=0)])
           for x in list(keys) + list(payload)]
    lane = jnp.arange(n, dtype=jnp.int32)
    s = n // 2
    while s >= 1:
        low = (lane & s) == 0
        other = [jnp.where(per_lane(low, x), shift_lanes(x, s),
                           shift_lanes(x, -s)) for x in ops]
        mine, theirs = ops[:len(keys)], other[:len(keys)]
        take = jnp.where(low, _lex_greater(mine, theirs),
                         _lex_greater(theirs, mine))
        ops = [jnp.where(per_lane(take, x), o, x)
               for x, o in zip(ops, other)]
        s //= 2
    return ops[:len(keys)], ops[len(keys):]


class _PairReducers:
    """Group reductions over sorted runs of AT MOST TWO rows (two
    states of unique keys, merged), each group's value moved to the
    front by the compress network: no scatter, no gather."""

    def __init__(self, boundary: jnp.ndarray, live: jnp.ndarray):
        #: lane i + 1 holds the second row of lane i's run
        self.pair = shift_lanes(live & ~boundary, 1)
        self.boundary = boundary
        self.moves, self.num_groups = compress_moves(boundary)

    def _with_second(self, x, fn):
        return self.front(jnp.where(per_lane(self.pair, x),
                                    fn(x, shift_lanes(x, 1)), x))

    def front(self, x):
        return compress_lanes(self.moves, x)

    def count(self, valid):
        return self.sum(valid.astype(jnp.int64))

    def sum(self, x):
        return self._with_second(x, jnp.add)

    def min(self, x):
        return self._with_second(x, jnp.minimum)

    def max(self, x):
        return self._with_second(x, jnp.maximum)

    def gather(self, per_group):
        gid = jnp.maximum(prefix_sum(self.boundary.astype(jnp.int32)) - 1, 0)
        return jnp.take(per_group, gid, axis=0)


class _RowReducers:
    """Every row a group of its own (one state of unique keys)."""

    count = staticmethod(lambda valid: valid.astype(jnp.int64))
    sum = min = max = gather = front = staticmethod(lambda x: x)


def merge_states(a: Batch, b: Batch, n_keys: int,
                 aggs: Sequence[AggSpec]) -> Tuple[Batch, jnp.ndarray]:
    """Two NORMALIZED states of one layout and capacity (and equal
    dictionaries: ``merge_network_ok`` and the caller see to that) as
    one, normalized, of twice the capacity: ``grouped_aggregate(...,
    mode="merge")`` over their concatenation, without its sort and its
    gathers. Beside the state, an int32 that says how: 1, one state
    ended before the other began (the partials of an input clustered by
    the keys) and the later stands behind the earlier, no lane compared
    with another; 0, their ranges overlap or meet in one key and the
    merge network ran. Seen on the device in the states' own first and
    last keys; the rows are the same either way."""
    keys_idx = list(range(n_keys))
    n_ops = n_keys + 1
    cap = a.capacity
    ops_a, ops_b = (
        _group_key_ops(s, keys_idx) + [c.data for c in s.columns[n_keys:]]
        for s in (a, b))
    count_a, count_b = a.count(), b.count()

    def ends(ops, count):
        # the first and the last live key tuple; an empty side reads a
        # dead lane's rank twice: it ends before nothing and everything
        # ends before it
        last = jnp.maximum(count - 1, 0)
        return ([x[0] for x in ops[:n_ops]],
                [jax.lax.dynamic_index_in_dim(x, last, keepdims=False)
                 for x in ops[:n_ops]])
    first_a, last_a = ends(ops_a, count_a)
    first_b, last_b = ends(ops_b, count_b)
    # strictly: a key that ends one state and begins the other is two
    # rows of ONE group, which only the network combines
    a_then_b = _lex_greater(first_b, last_a) | (count_b == 0)
    b_then_a = _lex_greater(first_a, last_b)

    def state(s_keys, s_state, s_mask, red, out_mask):
        front_rank = red.front(s_keys[0])
        key_cols = []
        for j, c in enumerate(a.columns[:n_keys]):
            data = red.front(s_keys[1 + j])
            if c.data.dtype == jnp.bool_:
                data = data.astype(jnp.bool_)
            valid = (front_rank & (1 << (n_keys - 1 - j))) == 0
            key_cols.append(Column(c.type, data, valid & out_mask,
                                   c.dictionary))
        seg = _segment_aggs(
            aggs, s_state, [s_mask] * len(s_state), s_mask, red,
            from_states=True,
            col_dicts=[c.dictionary for c in a.columns[n_keys:]])
        return _assemble(a, keys_idx, aggs, "merge", key_cols, seg, out_mask)

    def network():
        both = [jnp.concatenate([x, y]) for x, y in zip(ops_a, ops_b)]
        s_keys, s_state = _bitonic_merge(both[:n_ops], both[n_ops:])
        s_mask = s_keys[0] < _DEAD_RANK
        boundary, _, _ = _boundary_groups(s_keys, s_mask)
        red = _PairReducers(boundary, s_mask)
        return state(s_keys, s_state, s_mask, red,
                     jnp.arange(2 * cap) < red.num_groups)

    def appended(earlier, later, count):
        # the later state written at the lane where the earlier's live
        # rows end: its own dead lanes and the padding stand behind
        def behind(x, y, fill):
            pad = jnp.full((cap,) + x.shape[1:], fill, x.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                jnp.concatenate([x, pad]), y, count, axis=0)
        ops = [behind(x, y, _DEAD_RANK if i == 0 else 0)
               for i, (x, y) in enumerate(zip(earlier, later))]
        s_mask = ops[0] < _DEAD_RANK
        return state(ops[:n_ops], ops[n_ops:], s_mask, _RowReducers, s_mask)

    how = jnp.where(a_then_b, 1, jnp.where(b_then_a, 2, 0))
    merged = jax.lax.switch(
        how, [network,
              lambda: appended(ops_a, ops_b, count_a),
              lambda: appended(ops_b, ops_a, count_b)])
    return merged, (how > 0).astype(jnp.int32)


def finish_states(state: Batch, n_keys: int,
                  aggs: Sequence[AggSpec]) -> Batch:
    """A state of UNIQUE keys (any partial or merge output, normalized
    or not) finalized lane by lane: ``grouped_aggregate(...,
    mode="final")`` over it, without the sort."""
    cols = state.columns[n_keys:]
    seg = _segment_aggs(
        aggs, [c.data for c in cols], [c.validity for c in cols],
        state.row_mask, _RowReducers, from_states=True,
        col_dicts=[c.dictionary for c in cols])
    return _assemble(state, list(range(n_keys)), aggs, "final",
                     list(state.columns[:n_keys]), seg, state.row_mask)


def global_aggregate(
    batch: Batch, aggs: Sequence[AggSpec], mode: str = "single"
) -> Batch:
    """Aggregation without GROUP BY: one output row, even over empty input
    (reference AggregationOperator.java global aggregation semantics).
    'merge' consumes state columns and emits merged state columns."""
    assert mode in ("single", "partial", "final", "merge")
    if has_drain_agg(aggs) and mode == "single":
        # exact one-pass path (drain callers and string inputs); the
        # partial/merge/final modes below carry bounded histogram state
        regular = [a for a in aggs if a.fn not in DRAIN_FNS]
        base = global_aggregate(batch, regular, "single")
        computed = {}
        for shared in _drain_groups(aggs).values():
            for agg, res in zip(shared, _global_percentiles(batch, shared)):
                computed[id(agg)] = res
        out_cols2: List[Column] = []
        out_fields2: List[Tuple[str, Type]] = []
        ri = 0
        for agg in aggs:
            if agg.fn in DRAIN_FNS:
                data, valid = computed[id(agg)]
                dt = agg.output_type.storage_dtype
                out_fields2.append((agg.name or agg.fn, agg.output_type))
                out_cols2.append(Column(
                    agg.output_type,
                    jnp.zeros(128, dtype=dt).at[0].set(data.astype(dt)),
                    jnp.zeros(128, dtype=bool).at[0].set(valid),
                    batch.columns[agg.input].dictionary
                    if agg.output_type.is_string else None))
            else:
                out_cols2.append(base.columns[ri])
                out_fields2.append((base.schema.names[ri],
                                    base.schema.types[ri]))
                ri += 1
        return Batch(Schema(out_fields2), out_cols2, base.row_mask)
    cap = 128  # minimum bucket; one live row
    mask = batch.row_mask
    out_fields: List[Tuple[str, Type]] = []
    out_cols: List[Column] = []
    out_mask = jnp.arange(cap) < 1

    def pad(scalar, dtype):
        scalar = jnp.asarray(scalar)
        if scalar.ndim:                    # limb pairs and other vectors
            return jnp.zeros((cap,) + scalar.shape,
                             dtype=dtype).at[0].set(scalar.astype(dtype))
        return jnp.zeros(cap, dtype=dtype).at[0].set(scalar.astype(dtype))

    state_cursor = 0
    for agg in aggs:
        if agg.fn == "approx_percentile":
            from .sketch import QD_BINS, qd_estimate, qd_update
            if mode in ("final", "merge"):
                col = batch.columns[state_cursor]
                state_cursor += 1
                counts = jnp.sum(
                    jnp.where(mask[:, None], col.data,
                              jnp.zeros_like(col.data)), axis=0)
            else:
                c = batch.columns[agg.input]
                if c.dictionary is not None:
                    raise NotImplementedError(
                        "approx_percentile over strings is drain-only "
                        "(see percentile_drains)")
                valid = c.validity & mask
                if agg.mask is not None:
                    valid = valid & \
                        batch.columns[agg.mask].data.astype(bool)
                if getattr(c.data, "ndim", 1) == 2:
                    # long-decimal limbs: histogram over the f64 image
                    # of the unscaled value (monotone, so percentile
                    # bins land identically)
                    from . import int128 as I
                    counts = qd_update(valid, I.to_f64(c.data))
                else:
                    counts = qd_update(valid, c.data.astype(jnp.float64))
            if mode in ("partial", "merge"):
                (fname, ftype) = agg.state_types()[0]
                out_fields.append((fname, ftype))
                out_cols.append(Column(
                    ftype,
                    jnp.zeros((cap, QD_BINS), dtype=jnp.int64).at[0].set(
                        counts),
                    out_mask, None))
            else:
                p = float(agg.param if agg.param is not None else 0.5)
                val, ok = qd_estimate(counts, p)
                dt = agg.output_type.storage_dtype
                if isinstance(agg.output_type, T.DecimalType) \
                        and agg.output_type.is_long:
                    from . import int128 as I
                    val = I.from_f64(jnp.round(val))
                elif not jnp.issubdtype(dt, jnp.floating):
                    val = jnp.round(val)
                out_fields.append((agg.name or agg.fn, agg.output_type))
                out_cols.append(Column(
                    agg.output_type, pad(val, dt),
                    jnp.zeros(cap, dtype=bool).at[0].set(ok), None))
            continue
        if agg.fn == "approx_distinct":
            from .sketch import (hashed_column, hll_estimate, hll_m,
                                 hll_update)
            m = hll_m(agg.param)
            if mode in ("final", "merge"):
                cols = batch.columns[state_cursor:state_cursor + 1]
                state_cursor += 1
                regs = jnp.max(jnp.where(mask[:, None], cols[0].data, 0),
                               axis=0)
            else:
                c = batch.columns[agg.input]
                valid = c.validity & mask
                if agg.mask is not None:
                    valid = valid & \
                        batch.columns[agg.mask].data.astype(bool)
                hashed = hashed_column(c.data, c.dictionary)
                regs = hll_update(jnp.zeros(batch.capacity, jnp.int32),
                                  valid, hashed, 1, m)[0]
            if mode in ("partial", "merge"):
                (fname, ftype) = agg.state_types()[0]
                out_fields.append((fname, ftype))
                out_cols.append(Column(
                    ftype,
                    jnp.zeros((cap, m), dtype=jnp.int32).at[0].set(
                        regs.astype(jnp.int32)),
                    out_mask, None))
            else:
                out_fields.append((agg.name or agg.fn, agg.output_type))
                out_cols.append(Column(
                    agg.output_type, pad(hll_estimate(regs), jnp.int64),
                    jnp.zeros(cap, dtype=bool).at[0].set(True), None))
            continue
        if mode in ("final", "merge"):
            n_state = len(agg.state_types())
            cols = batch.columns[state_cursor:state_cursor + n_state]
            state_cursor += n_state
            if agg.fn in ("count", "count_star"):
                cnt = jnp.sum(jnp.where(mask, cols[0].data, 0))
                parts: Tuple[jnp.ndarray, ...] = (cnt,)
            elif agg.fn in _VARIANCE_FNS:
                # corrected merge of (mean, m2, n) partials — see
                # _segment_aggs
                cnt_raw = cols[2].data
                live = mask & (cnt_raw > 0)
                nf = jnp.where(live, cnt_raw, 0).astype(jnp.float64)
                cnt = jnp.sum(jnp.where(mask, cnt_raw, 0))
                n = jnp.maximum(cnt, 1).astype(jnp.float64)
                mean = jnp.sum(nf * jnp.where(live, cols[0].data, 0.0)) / n
                dev = cols[0].data - mean
                wdev = jnp.sum(jnp.where(live, nf * dev, 0.0))
                m2 = jnp.sum(jnp.where(
                    live, cols[1].data + nf * dev * dev,
                    0.0)) - wdev * wdev / n
                parts = (mean + wdev / n, m2, cnt)
            elif isinstance(agg.state_types()[0][1], T.DecimalType) \
                    and agg.state_types()[0][1].is_long:
                from . import int128 as I
                cnt_raw = cols[1].data
                live = mask & (cnt_raw > 0)
                cnt = jnp.sum(jnp.where(mask, cnt_raw, 0))
                v = cols[0].data               # [n, 2] limb states
                if agg.fn in ("sum", "avg"):
                    val = _checked_sum128(
                        v, live, lambda p: jnp.sum(p, axis=0))
                else:
                    val = _minmax128_scalar(v, live, agg.fn)
                parts = (val, cnt)
            else:
                cnt_raw = cols[1].data
                live = mask & (cnt_raw > 0)
                cnt = jnp.sum(jnp.where(mask, cnt_raw, 0))
                v = cols[0].data
                if (agg.fn in ("min", "max")
                        and cols[0].dictionary is not None):
                    val = _rank_reduce_scalar(v, live, cols[0].dictionary,
                                              agg.fn)
                elif agg.fn in ("sum", "avg", "bool_and", "bool_or"):
                    if agg.fn == "bool_and":
                        val = jnp.min(jnp.where(live, v,
                                                _max_sentinel(v.dtype)))
                    elif agg.fn == "bool_or":
                        val = jnp.max(jnp.where(live, v,
                                                _min_sentinel(v.dtype)))
                    else:
                        val = jnp.sum(jnp.where(live, v,
                                                jnp.zeros_like(v)))
                elif agg.fn == "min":
                    val = jnp.min(jnp.where(live, v, _max_sentinel(v.dtype)))
                else:
                    val = jnp.max(jnp.where(live, v, _min_sentinel(v.dtype)))
                parts = (val, cnt)
        else:
            if agg.fn == "count_star":
                parts = (jnp.sum(mask.astype(jnp.int64)),)
            else:
                c = batch.columns[agg.input]
                valid = c.validity & mask
                if agg.mask is not None:
                    valid = valid & \
                        batch.columns[agg.mask].data.astype(bool)
                cnt = jnp.sum(valid.astype(jnp.int64))
                if agg.fn == "count":
                    parts = (cnt,)
                elif agg.fn in _VARIANCE_FNS:
                    # corrected two-pass central moments (see
                    # _segment_aggs)
                    x = c.data.astype(jnp.float64)
                    n = jnp.maximum(cnt, 1).astype(jnp.float64)
                    mean = jnp.sum(jnp.where(valid, x, 0.0)) / n
                    dev = jnp.where(valid, x - mean, 0.0)
                    s1 = jnp.sum(dev)
                    parts = (mean + s1 / n,
                             jnp.sum(dev * dev) - s1 * s1 / n, cnt)
                elif agg.fn in ("bool_and", "bool_or"):
                    x = c.data.astype(jnp.int32)
                    if agg.fn == "bool_and":
                        val = jnp.min(jnp.where(valid, x, jnp.int32(1)))
                    else:
                        val = jnp.max(jnp.where(valid, x, jnp.int32(0)))
                    parts = (val, cnt)
                elif (agg.fn in ("min", "max")
                      and c.dictionary is not None):
                    val = _rank_reduce_scalar(c.data, valid, c.dictionary,
                                              agg.fn)
                    parts = (val, cnt)
                elif isinstance(agg.state_types()[0][1], T.DecimalType) \
                        and agg.state_types()[0][1].is_long:
                    from . import int128 as I
                    x = c.data if c.data.ndim == 2 else I.from_i64(c.data)
                    if agg.fn in ("sum", "avg"):
                        val = _checked_sum128(
                            x, valid, lambda p: jnp.sum(p, axis=0))
                    else:
                        val = _minmax128_scalar(x, valid, agg.fn)
                    parts = (val, cnt)
                else:
                    acc_dtype = agg.state_types()[0][1].storage_dtype
                    x = c.data.astype(acc_dtype)
                    if agg.fn in ("sum", "avg"):
                        val = jnp.sum(jnp.where(valid, x, jnp.zeros_like(x)))
                    elif agg.fn == "min":
                        val = jnp.min(jnp.where(valid, x, _max_sentinel(acc_dtype)))
                    else:
                        val = jnp.max(jnp.where(valid, x, _min_sentinel(acc_dtype)))
                    parts = (val, cnt)
        vd = None
        if agg.fn in ("min", "max") and agg.input is not None:
            if mode in ("final", "merge"):
                vd = cols[0].dictionary
            else:
                vd = batch.columns[agg.input].dictionary
        if mode in ("partial", "merge"):
            for (fname, ftype), arr in zip(agg.state_types(), parts):
                out_fields.append((fname, ftype))
                out_cols.append(Column(
                    ftype, pad(arr, ftype.storage_dtype), out_mask,
                    vd if ftype.is_string else None))
        else:
            if agg.fn in ("count", "count_star"):
                data, valid = parts[0], jnp.asarray(True)
            else:
                data, valid = _finalize_scalar(agg, parts)
            name = agg.name or agg.fn
            out_fields.append((name, agg.output_type))
            dt = agg.output_type.storage_dtype
            out_cols.append(Column(
                agg.output_type, pad(data, dt),
                jnp.zeros(cap, dtype=bool).at[0].set(valid),
                vd if agg.output_type.is_string else None))
    return Batch(Schema(out_fields), out_cols, out_mask)


def _minmax128_scalar(x: jnp.ndarray, live: jnp.ndarray,
                      fn: str) -> jnp.ndarray:
    """Global min/max over int128 limb tiles [n, 2] -> [2]."""
    return _minmax128(x, live, _GlobalReducer(), fn)


def _finalize_scalar(agg: AggSpec, parts):
    if agg.fn in _VARIANCE_FNS:
        return _variance_out(agg, *parts)
    val, cnt = parts
    valid = cnt > 0
    if agg.fn in ("bool_and", "bool_or"):
        return val > 0, valid
    if val.ndim == 1 and val.shape == (2,) \
            and agg.fn in ("sum", "avg", "min", "max") \
            and isinstance(agg.state_types()[0][1], T.DecimalType) \
            and agg.state_types()[0][1].is_long:
        return _finalize_dec128(agg, val, cnt), valid
    if agg.fn == "avg":
        if isinstance(agg.output_type, T.DecimalType):
            den = jnp.maximum(cnt, 1)
            out = (jnp.sign(val) * jnp.floor(jnp.abs(val) / den + 0.5)).astype(jnp.int64)
            return out, valid
        return val / jnp.maximum(cnt, 1).astype(val.dtype), valid
    return val, valid
