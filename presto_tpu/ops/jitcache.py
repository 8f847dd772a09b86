"""Cached jit entry points for the relational kernels.

The analogue of the reference's compiled-operator caches (reference
sql/gen/PageFunctionCompiler.java:121-136 caches generated classes per
expression): each (static-args) combination compiles once, and every
batch with the same shape bucket reuses the executable. Without this the
local executor dispatches each lax primitive eagerly — per-op overhead
dominates once batches hit millions of rows.

Batch is a registered pytree whose aux data includes column types and
dictionaries, so a new dictionary tuple (rare: dictionaries are stable
per column for generator connectors) simply retraces that one call.
"""
from __future__ import annotations

import dataclasses
import functools
import re
import threading
import types as _pytypes
from typing import Optional, Sequence

import jax

from .._devtools import lockcheck as _lockcheck
from ..obs import profiler as _prof
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER, device_drained, note_launch
from .aggregation import (
    AggSpec, finish_states, global_aggregate, grouped_aggregate, merge_states)

_JIT_HITS = REGISTRY.counter("jit_cache_hits_total")
_JIT_MISSES = REGISTRY.counter("jit_cache_misses_total")

#: what a program of the engine may be called: one of three prefixes
#: (``op_`` a jit-cache entry, ``expr_`` an expression program,
#: ``smap_`` a mesh program), then ``[a-z0-9_]``. XLA names the module
#: ``jit_<name>``, and so do the profiler's trace, JAX's compile event
#: and the persistent compile cache's key: the name must be the same in
#: every process and for every checkout. Whatever the device trace shows
#: under another name (``jit_scatter-add``, ``jit__take``) is by that
#: fact an eager op outside every jit of the engine.
_PROGRAM_NAME = re.compile(r"(op|expr|smap)_[a-z0-9_]+")


def program_name(prefix: str, label: str) -> str:
    """``<prefix>_<label>`` with the label folded to ``[a-z0-9_]``."""
    return f"{prefix}_" + re.sub(r"[^a-z0-9]+", "_", label.lower()).strip("_")


def named_jit(name: str, fn, **jit_kw):
    """``jax.jit`` of ``fn`` under the program name ``name``: the one
    place the engine jits, so that no program reaches XLA as ``jit_run``
    or ``jit__lambda_``. ``fn`` takes positional arguments only (the
    wrapper that carries the name passes nothing else on) and is left
    as it was."""
    if not _PROGRAM_NAME.fullmatch(name):
        raise ValueError(f"program name {name!r}: want op_/expr_/smap_ "
                         f"then [a-z0-9_]")

    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kw)


#: sentinel: a closure captured something we cannot prove is
#: value-stable, so the program must not be shared across queries
_SIG_MISS = object()

#: recursion ceiling for closure fingerprints — deep enough for a plan
#: subtree hanging off a probe closure, cheap enough to run once per
#: program construction
_SIG_MAX_DEPTH = 32


def _value_sig(v, depth: int, seen) -> object:
    """Hashable value-identity of one captured object, or ``_SIG_MISS``.

    The contract that makes cross-query program sharing safe: two equal
    signatures mean the closures compute the SAME traced function for
    equal input avals. Only value-immutable things get a signature —
    primitives, tuples/lists of them, frozen dataclasses (the whole
    plan/expr/type system: PlanNode, ir.Expr, Type, AggSpec, Field),
    Schema, and nested pure-python functions (their code object +
    recursively-fingerprinted cells/defaults). Anything
    identity-hashable or mutable (executors, repartitioners, arrays,
    dicts) yields ``_SIG_MISS`` and the program keeps today's
    compile-per-query behavior — a miss is never wrong, only slower."""
    if depth > _SIG_MAX_DEPTH:
        return _SIG_MISS
    if v is None or v is True or v is False:
        return v
    t = type(v)
    if t in (int, float, str, bytes, complex):
        return (t.__name__, v)
    if t in (tuple, list):
        parts = tuple(_value_sig(x, depth + 1, seen) for x in v)
        if any(p is _SIG_MISS for p in parts):
            return _SIG_MISS
        return (t.__name__,) + parts
    if t is frozenset:
        parts = tuple(_value_sig(x, depth + 1, seen)
                      for x in sorted(v, key=repr))
        if any(p is _SIG_MISS for p in parts):
            return _SIG_MISS
        return ("frozenset",) + parts
    if t is _pytypes.FunctionType:
        return _fn_sig(v, depth + 1, seen)
    if t is functools.partial:
        parts = (_value_sig(v.func, depth + 1, seen),
                 _value_sig(tuple(v.args), depth + 1, seen),
                 _value_sig(tuple(sorted(v.keywords.items())),
                            depth + 1, seen))
        if any(p is _SIG_MISS for p in parts):
            return _SIG_MISS
        return ("partial",) + parts
    if t is _pytypes.BuiltinFunctionType:
        return ("bfn", getattr(v, "__module__", None), v.__qualname__)
    from ..batch import Schema
    if t is Schema:
        return ("schema", v.fields)
    if dataclasses.is_dataclass(v) and not isinstance(v, type) \
            and v.__dataclass_params__.frozen:
        parts = tuple(_value_sig(getattr(v, f.name), depth + 1, seen)
                      for f in dataclasses.fields(v))
        if any(p is _SIG_MISS for p in parts):
            return _SIG_MISS
        return ("dc", t) + parts
    return _SIG_MISS


def _fn_sig(fn, depth: int, seen) -> object:
    code = getattr(fn, "__code__", None)
    if code is None or id(fn) in seen:
        return _SIG_MISS
    seen = seen | {id(fn)}
    parts = [code]
    try:
        cells = fn.__closure__ or ()
        for cell in cells:
            parts.append(_value_sig(cell.cell_contents, depth + 1, seen))
    except ValueError:            # empty cell (still-initializing def)
        return _SIG_MISS
    for d in (fn.__defaults__ or ()):
        parts.append(_value_sig(d, depth + 1, seen))
    if any(p is _SIG_MISS for p in parts):
        return _SIG_MISS
    return ("fn",) + tuple(parts)


def program_signature(fn) -> Optional[object]:
    """Hashable cross-query identity of a program-defining closure, or
    None when it captures anything that is not provably value-stable.
    The mesh executor keys its shard_map program cache on this: the
    warm-up run of a query shape traces + compiles once, and every
    later query with the same shape dispatches the SAME executable
    instead of paying a fresh trace (the last head of the per-query
    dispatch tax after the fused exchange removed the per-round one)."""
    sig = _fn_sig(fn, 0, frozenset())
    return None if sig is _SIG_MISS else sig


class _TimedEntry:
    """Jitted callable with a ledger: every entry owns an
    ExecutableRecord in ``obs.profiler.EXECUTABLES`` (invocations here;
    compiles and compile seconds from the profiler's compile listener,
    which knows the record by the program this thread launched last).
    While the tracer is on each launch is a ``dispatch`` span; under a
    profile context it is additionally bracketed with
    block_until_ready and attributed to the operator whose frame made
    the call. Build one with :func:`timed_entry`, which names the
    program after the entry."""

    __slots__ = ("name", "fn", "program", "first", "record", "donate")

    def __init__(self, name: str, fn, key=(), donate=()):
        self.name = name
        self.fn = fn
        fun = getattr(fn, "__name__", name)
        #: the XLA module's name, as the device trace shows it
        self.program = "jit_" + re.sub(r"[^A-Za-z0-9_]", "_", fun)
        self.first = True
        #: argument positions this executable DONATES (built with
        #: ``jax.jit(donate_argnums=...)``): callers must treat those
        #: inputs as consumed — the round-carried shard buffers of the
        #: fused exchange loops alias their outputs instead of churning
        #: HBM, and the donated arrays are deleted on dispatch
        self.donate = tuple(donate)
        self.record = _prof.EXECUTABLES.register(name, key, program=fun)

    def __call__(self, *args):
        if _lockcheck.ENABLED:
            # an engine lock held across a device dispatch serializes
            # every other query behind this one's kernels — the runtime
            # lock validator fails the suite on it
            _lockcheck.note_dispatch(self.name)
        _prof.note_launch(self.record)
        _prof.INVOCATIONS.inc()
        if self.first:
            self.first = False
            self.record.capture_avals(self.fn, args)
        if TRACER.enabled:
            # starved: had the device nothing queued when this launch
            # began? (obs/trace.device_drained; None where unknown)
            with TRACER.span("dispatch", program=self.program,
                             starved=device_drained()):
                return note_launch(self._launch(args))
        return self._launch(args)

    def _launch(self, args):
        if _prof.profiling_active():
            return _prof.profiled_call(self.record, self.fn, args)
        return self.fn(*args)


def timed_entry(name: str, fn, key=(), donate=(), program=None,
                **jit_kw) -> _TimedEntry:
    """The :class:`_TimedEntry` of the plain function ``fn``, jitted
    under the program name ``op_<name>`` (or ``program``, for the mesh
    programs' ``smap_`` names): the record's name and XLA's are given
    in one place and cannot drift."""
    if donate:
        jit_kw["donate_argnums"] = tuple(donate)
    return _TimedEntry(
        name, named_jit(program or program_name("op", name), fn, **jit_kw),
        key, donate)


def _entry_cache(name: str, factory):
    """lru_cache replacement for the jit entry points: per-(static-args)
    memo plus cache-hit/miss counters — the metrics feed the reference
    exposes from PageFunctionCompiler's cache stats. ``factory`` returns
    the plain function; it is jitted here, as ``op_<name>``."""
    cache = {}
    lock = threading.Lock()

    def get(*key):
        fn = cache.get(key)
        if fn is None:
            with lock:
                fn = cache.get(key)
                if fn is None:
                    _JIT_MISSES.inc()
                    fn = cache[key] = timed_entry(name, factory(*key),
                                                  key)
                    return fn
        _JIT_HITS.inc()
        return fn
    return get


def _grouped_factory(group_indices, aggs, mode, output_capacity,
                     key_bounds, allow_dense, ordered=False):
    def run(batch):
        return grouped_aggregate(batch, group_indices, aggs, mode,
                                 output_capacity, allow_dense=allow_dense,
                                 key_bounds=key_bounds)

    def run_ordered(batch):
        # (the state, the scalar that says whether the batch stood in
        # the keys' order: 0 where the dense path took it, which asks
        # for no order)
        import jax.numpy as jnp
        flag: list = []
        out = grouped_aggregate(batch, group_indices, aggs, mode,
                                output_capacity, allow_dense=allow_dense,
                                key_bounds=key_bounds, order_violation=flag)
        return out, (flag[0] if flag else jnp.int32(0))
    return run_ordered if ordered else run


_grouped = _entry_cache("grouped_aggregate", _grouped_factory)


def grouped_aggregate_jit(batch, group_indices: Sequence[int],
                          aggs: Sequence[AggSpec], mode: str = "single",
                          output_capacity: Optional[int] = None,
                          key_bounds=None, allow_dense: bool = True,
                          ordered: bool = False):
    """``ordered``: the batch's live rows stand in the keys' order, the
    planner says; the answer is then (state, order-violation scalar)."""
    return _grouped(tuple(group_indices), tuple(aggs), mode,
                    output_capacity,
                    tuple(key_bounds) if key_bounds else None,
                    allow_dense, ordered)(batch)


def _merge_pair_factory(group_indices, aggs, key_bounds, allow_dense):
    def run(a, b):
        from ..batch import concat_batches
        return grouped_aggregate(concat_batches([a, b]), group_indices,
                                 aggs, "merge", None,
                                 allow_dense=allow_dense,
                                 key_bounds=key_bounds)
    return run


_merge_pair = _entry_cache("grouped_aggregate_pair", _merge_pair_factory)


def merge_pair_jit(a, b, group_indices: Sequence[int],
                   aggs: Sequence[AggSpec], key_bounds=None,
                   allow_dense: bool = True):
    """Two state batches of one layout and equal dictionaries merged by
    ``grouped_aggregate`` over their concatenation, INSIDE the program
    (``AggSpillBuffer``'s merge where the network cannot take the
    pair)."""
    return _merge_pair(tuple(group_indices), tuple(aggs),
                       tuple(key_bounds) if key_bounds else None,
                       allow_dense)(a, b)


_merge_states = _entry_cache(
    "grouped_aggregate_merge",
    lambda n_keys, aggs: lambda a, b: merge_states(a, b, n_keys, aggs))


def merge_states_jit(a, b, n_keys: int, aggs: Sequence[AggSpec]):
    """``ops.aggregation.merge_states``: two normalized states of one
    capacity as one, without a sort or a gather, and the device int32
    that says whether one was appended to the other (1) or the network
    ran (0)."""
    return _merge_states(n_keys, tuple(aggs))(a, b)


_finish_states = _entry_cache(
    "grouped_aggregate_finish",
    lambda n_keys, aggs: lambda s: finish_states(s, n_keys, aggs))


def finish_states_jit(state, n_keys: int, aggs: Sequence[AggSpec]):
    """``ops.aggregation.finish_states``: a state of unique keys
    finalized lane by lane."""
    return _finish_states(n_keys, tuple(aggs))(state)


_prefix = _entry_cache(
    "prefix", lambda capacity: lambda b: b.prefix(capacity))


def prefix_jit(batch, capacity: int):
    """The first ``capacity`` lanes of a batch whose live rows come
    first (a sort-path state): a slice, where ``compact_jit`` gathers."""
    return _prefix(capacity)(batch)


def _bounds_violation_factory(group_indices, key_bounds):
    import jax.numpy as jnp

    from ..errors import STATS_BOUND_VIOLATION

    def run(b):
        bad = jnp.zeros((), dtype=bool)
        for gi, kb in zip(group_indices, key_bounds):
            if kb is None:
                continue
            c = b.columns[gi]
            data = c.data.astype(jnp.int64)
            out = (b.row_mask & c.validity
                   & ((data < kb[0]) | (data > kb[1])))
            bad = bad | jnp.any(out)
        return jnp.where(bad, jnp.int32(STATS_BOUND_VIOLATION),
                         jnp.int32(0))
    return run


_bounds_violation = _entry_cache("key_bounds_violation",
                                 _bounds_violation_factory)


def key_bounds_violation_jit(batch, group_indices, key_bounds):
    """Device scalar (error code or 0) marking live, valid group keys
    outside their stats-promised [lo, hi]. The dense composite-code
    kernel CLAMPS such keys to stay in-bounds, so the executor must
    append this scalar to its error-flag channel — the query then fails
    with STATS_BOUND_VIOLATION instead of returning misgrouped rows. No
    readback here: flags sync once per query (check_errors)."""
    return _bounds_violation(tuple(group_indices),
                             tuple(key_bounds))(batch)


def _global_factory(aggs, mode):
    def run(batch):
        return global_aggregate(batch, aggs, mode)
    return run


_global = _entry_cache("global_aggregate", _global_factory)


def global_aggregate_jit(batch, aggs: Sequence[AggSpec],
                         mode: str = "single"):
    return _global(tuple(aggs), mode)(batch)


# -- join kernels ------------------------------------------------------------
# (reference: HashBuilderOperator builds one LookupSource reused by every
# probe; here prepare_build_jit sorts the build once and the probe-side
# kernels take the prepared arrays as a pytree argument)

from .join import (  # noqa: E402
    build_key_ranks, build_match_mask, expand_join, lookup_join,
    match_count_max, prepare_build, semi_join_mask,
)


_prepare = _entry_cache(
    "prepare_build",
    lambda key_cols: lambda b: prepare_build(b, key_cols))


def prepare_build_jit(build, key_cols):
    return _prepare(tuple(key_cols))(build)


_lookup = _entry_cache(
    "lookup_join",
    lambda pkeys, bkeys, payload, names, jt: (
        lambda p, b, prep: lookup_join(
            p, b, pkeys, bkeys, payload, names, jt, prepared=prep)))


def lookup_join_jit(probe, build, probe_keys, build_keys, payload,
                    payload_names, join_type, prepared):
    return _lookup(tuple(probe_keys), tuple(build_keys), tuple(payload),
                   tuple(payload_names), join_type)(probe, build, prepared)


_expand = _entry_cache(
    "expand_join",
    lambda pkeys, bkeys, payload, names, jt, max_matches: (
        lambda p, b, prep: expand_join(
            p, b, pkeys, bkeys, payload, names, jt, max_matches,
            prepared=prep)))


def expand_join_jit(probe, build, probe_keys, build_keys, payload,
                    payload_names, join_type, max_matches, prepared):
    return _expand(tuple(probe_keys), tuple(build_keys), tuple(payload),
                   tuple(payload_names), join_type,
                   max_matches)(probe, build, prepared)


_match_count = _entry_cache(
    "match_count_max",
    lambda pkeys, bkeys: lambda p, b, prep: match_count_max(
        p, b, pkeys, bkeys, prepared=prep))


def match_count_max_jit(probe, build, probe_keys, build_keys, prepared):
    return _match_count(tuple(probe_keys),
                        tuple(build_keys))(probe, build, prepared)


from .join import max_multiplicity  # noqa: E402

#: max build-key multiplicity of a prepared build — ONE readback per
#: build, replacing the per-probe-batch match_count_max syncs for
#: non-skewed builds (jit retraces per prepared-pytree structure, so one
#: wrapper covers both the direct and sorted layouts)
max_multiplicity_jit = timed_entry("max_multiplicity", max_multiplicity)


_match_mask = _entry_cache(
    "build_match_mask",
    lambda pkeys, bkeys: lambda p, b, prep: build_match_mask(
        p, b, pkeys, bkeys, prepared=prep))


def build_match_mask_jit(probe, build, probe_keys, build_keys, prepared):
    return _match_mask(tuple(probe_keys),
                       tuple(build_keys))(probe, build, prepared)


_key_ranks = _entry_cache(
    "build_key_ranks",
    lambda key_cols: lambda b, prep: build_key_ranks(
        b, key_cols, prepared=prep))


def build_key_ranks_jit(build, key_cols, prepared):
    return _key_ranks(tuple(key_cols))(build, prepared)


_semi = _entry_cache(
    "semi_join_mask",
    lambda skeys, fkeys, negated, null_aware: (
        lambda p, b, prep: semi_join_mask(
            p, b, skeys, fkeys, negated, null_aware, prepared=prep)))


def semi_join_mask_jit(probe, build, probe_keys, build_keys,
                       negated, null_aware, prepared):
    return _semi(tuple(probe_keys), tuple(build_keys), negated,
                 null_aware)(probe, build, prepared)


_compact = _entry_cache(
    "compact",
    lambda capacity: lambda b: b.compact(capacity, check=False))


def compact_jit(batch, capacity: int):
    """Jitted Batch.compact: the live count must fit ``capacity``."""
    _COMPACT_PROGRAMS.inc()
    return _compact(capacity)(batch)


_pad = _entry_cache(
    "pad_capacity",
    lambda capacity: lambda b: b.pad(capacity))


def pad_capacity_jit(batch, capacity: int):
    """Jitted Batch.pad — grow a ragged batch (a split's residual final
    chunk) to the scan stream's standard bucket with dead lanes, so
    downstream operators reuse one executable per shape instead of
    compiling one per residual size."""
    return _pad(capacity)(batch)


from .join import prepare_direct  # noqa: E402


_prepare_direct = _entry_cache(
    "prepare_direct",
    lambda key_cols, size, unique=False: (
        lambda b, lo0: prepare_direct(b, key_cols, lo0, size, unique)))


def prepare_direct_jit(build, key_cols, lo0, size: int,
                       unique: bool = False):
    return _prepare_direct(tuple(key_cols), size, unique)(build, lo0)


from .join import prepare_direct_keyed  # noqa: E402


_prepare_direct_keyed = _entry_cache(
    "prepare_direct_keyed",
    lambda key_cols, los, sizes, size, unique=False: (
        lambda b: prepare_direct_keyed(b, key_cols, los, sizes, size,
                                       unique)))


def prepare_direct_keyed_jit(build, key_cols, los, sizes, size: int,
                             unique: bool = False):
    """Planner-bounded multi-key direct table: los/sizes/size are
    host-static (from JoinNode.key_bounds), so the table capacity — and
    every probe executable shape over it — is known at plan time."""
    return _prepare_direct_keyed(tuple(key_cols), tuple(los),
                                 tuple(sizes), size, unique)(build)


from .join import pack_sorted_payload  # noqa: E402

_pack_payload = _entry_cache(
    "pack_sorted_payload",
    lambda payload, in_order: lambda b, prep: pack_sorted_payload(
        b, payload, prep, in_order))


def pack_sorted_payload_jit(build, payload, prepared, in_order=False):
    """``ops.join.pack_sorted_payload``: once a unique build whose
    residual semi join reads ``payload`` of the one match."""
    return _pack_payload(tuple(payload), in_order)(build, prepared)


def _lookup_pallas_factory(pkeys, bkeys, payload, names, jt):
    from .pallas_join import lookup_join_direct

    def run(p, b, prep):
        return lookup_join_direct(p, b, pkeys, bkeys, payload, names,
                                  jt, prep)
    return run


_lookup_pallas = _entry_cache("lookup_join_pallas", _lookup_pallas_factory)


def lookup_join_pallas_jit(probe, build, probe_keys, build_keys, payload,
                           payload_names, join_type, prepared):
    """The Pallas probe-kernel twin of lookup_join_jit (direct prepared
    only — callers gate on the join_pallas_probe session property and
    ops/pallas_join.supports_join; a kernel failure propagates)."""
    return _lookup_pallas(tuple(probe_keys), tuple(build_keys),
                          tuple(payload), tuple(payload_names),
                          join_type)(probe, build, prepared)


def _build_summary_factory(key_cols, int_flags):
    import jax.numpy as jnp

    def run(b):
        live = b.row_mask
        out = [jnp.sum(live.astype(jnp.int64))]
        for k, is_int in zip(key_cols, int_flags):
            if not is_int:
                out += [jnp.int64(0), jnp.int64(-1)]
                continue
            c = b.columns[k]
            ok = live & c.validity
            data = c.data.astype(jnp.int64)
            out.append(jnp.min(jnp.where(ok, data,
                                         jnp.iinfo(jnp.int64).max)))
            out.append(jnp.max(jnp.where(ok, data,
                                         jnp.iinfo(jnp.int64).min)))
        return jnp.stack(out)
    return run


_build_summary = _entry_cache("build_summary", _build_summary_factory)


def build_summary_jit(build, key_cols, int_flags):
    """One fused device reduction for everything the executor needs to
    know about a drained join build: [live_count, (lo, hi) per key].
    Non-integer keys report (0, -1). The caller reads it back ONCE —
    every separate readback stalls the host until the queued async
    work drains, and the three values (live count, direct-table bounds,
    dynamic-filter bounds) are needed at the same point."""
    return _build_summary(tuple(key_cols), tuple(int_flags))(build)


from .join import expand_match_origins, unique_match_build_mask  # noqa: E402


_unique_match_build = _entry_cache(
    "unique_match_build_mask",
    lambda pkeys, bkeys: (
        lambda p, b, s, prep: unique_match_build_mask(
            p, b, pkeys, bkeys, s, prepared=prep)))


def unique_match_build_mask_jit(probe, build, probe_keys, build_keys,
                                survived, prepared):
    return _unique_match_build(tuple(probe_keys), tuple(build_keys))(
        probe, build, survived, prepared)


_expand_origins = _entry_cache(
    "expand_match_origins",
    lambda pkeys, bkeys, k: (
        lambda p, b, prep: expand_match_origins(
            p, b, pkeys, bkeys, k, prepared=prep)))


def expand_match_origins_jit(probe, build, probe_keys, build_keys,
                             max_matches, prepared):
    return _expand_origins(tuple(probe_keys), tuple(build_keys),
                           max_matches)(probe, build, prepared)


# -- the compaction program's launches ---------------------------------------
# Down here because a line that MOVES in this file above the Pallas
# kernels' frames re-keys `op_grouped_aggregate` (PERF.md section 7,
# row 3).

#: every launch of `op_compact`, whatever the site (`compact_jit`): the
#: executor's `_compactor`, the fused chain, TopN, the build sides, the
#: merge buffer; `compact_applied_total` counts the `_compactor`'s alone
_COMPACT_PROGRAMS = REGISTRY.counter("compact_programs_total")
