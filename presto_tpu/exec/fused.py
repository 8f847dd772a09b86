"""Whole-pipeline fusion: one jitted program per join probe pipeline.

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
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax.numpy as jnp

from ..batch import Batch, Column, Schema
from ..expr import ir
from ..expr.compiler import Val, eval_expr, merge_err
from .. import types as T  # noqa: F401  (type objects live in stage fields)
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
