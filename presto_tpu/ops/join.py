"""Join kernels: sorted-lookup equi-join.

The TPU-native replacement for Presto's hash join (reference
presto-main/.../operator/HashBuilderOperator.java:51, LookupJoinOperator.java,
PagesHash.java, JoinProbe.java): the build side is sorted by key on device
once; each probe row binary-searches it (``jnp.searchsorted``, O(log n)
vectorized across all probe lanes) and gathers the payload. Static shapes
throughout: the output has the probe's capacity, with the row mask narrowed
for misses (inner) or payload validity cleared (left outer).

``lookup_join`` assumes *unique build keys* — the PK-FK joins that dominate
TPC-H/TPC-DS; ``expand_join`` handles many-to-many with a static expansion
factor. Key tuples of any arity compare lexicographically (per-column i64 /
IEEE-total-order u64 operands + a vectorized composite binary search) — the
same generality as Presto's compiled channel-tuple comparators
(sql/gen/JoinCompiler.java).

SQL semantics: NULL keys never match (either side).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .prefix import prefix_sum
from .. import types as T
from ..batch import Batch, Column, Schema


def _key_sentinel(dtype):
    if dtype == jnp.uint64:
        return jnp.asarray(jnp.iinfo(jnp.uint64).max, dtype=jnp.uint64)
    return jnp.asarray(jnp.iinfo(jnp.int64).max, dtype=jnp.int64)


def _key_arrays(batch: Batch, key_cols: Sequence[int]
                ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Per-column comparable key operands + combined key validity.

    Integer-family columns (ints, dates, decimals, dictionary codes,
    booleans) become i64; floating columns map through the IEEE-754
    total-order bit trick to u64 (monotone, exact — no truncation). Any
    arity is supported; tuples compare lexicographically downstream
    (reference sql/gen/JoinCompiler.java hashes/compares arbitrary
    channel tuples)."""
    ops: List[jnp.ndarray] = []
    valid: Optional[jnp.ndarray] = None
    for i in key_cols:
        c = batch.columns[i]
        d = c.data
        if getattr(d, "ndim", 1) == 2:
            # long-decimal limb pairs: two lexicographic operands
            # (signed hi, unsigned-ordered lo) — downstream compares
            # key tuples generically, so arity just grows by one
            from .int128 import SIGN64
            ops.append(d[..., 0])
            ops.append(d[..., 1] ^ SIGN64)
            valid = c.validity if valid is None else valid & c.validity
            continue
        if jnp.issubdtype(d.dtype, jnp.floating):
            # +0.0 canonicalization (-0.0 + 0.0 == +0.0): SQL equality
            # joins the two zeros. NaN keys compare by bit pattern
            # (self-equal), i.e. grouping semantics.
            d = d.astype(jnp.float64) + 0.0
            bu = jax.lax.bitcast_convert_type(d, jnp.uint64)
            top = jnp.uint64(1) << jnp.uint64(63)
            d = jnp.where((bu >> jnp.uint64(63)) == 0, bu | top, ~bu)
        elif d.dtype == jnp.bool_:
            d = d.astype(jnp.int64)
        else:
            d = d.astype(jnp.int64)
        ops.append(d)
        valid = c.validity if valid is None else valid & c.validity
    return ops, valid


def build_sorted(build: Batch, key_cols: Sequence[int]):
    """Sort the build side lexicographically by the key tuple; dead and
    null-key rows to the end (their operands overwritten with per-dtype
    max sentinels, so the arrays stay fully sorted).

    Returns (sorted_key_ops, sorted_live, permutation) for probing; the
    permutation reorders build payload columns on demand.
    """
    ops, kvalid = _key_arrays(build, key_cols)
    live = build.row_mask & kvalid
    dead_rank = jnp.where(live, 0, 1).astype(jnp.int32)
    idx = jnp.arange(build.capacity, dtype=jnp.int32)
    out = jax.lax.sort([dead_rank] + ops + [idx], num_keys=1 + len(ops),
                       is_stable=True)
    perm = out[-1]
    slive = jnp.take(live, perm, axis=0)
    s_ops = [jnp.where(slive, op, _key_sentinel(op.dtype))
             for op in out[1:-1]]
    return s_ops, slive, perm


def build_in_order(build: Batch, key_cols: Sequence[int]):
    """``build_sorted``'s triple for a build that holds every key ONCE
    and is addressed through a direct table alone: the rows as they
    stand (the identity permutation, dead and null-key rows where they
    are, under the sentinel). A direct table finds a key's run by its
    slot, and a run of one row is contiguous in any order, so nothing
    has to be sorted (at 2^24 lanes the sort compiles for 140 s for a
    described v5e); a binary search or compare-all needs
    ``build_sorted``."""
    ops, kvalid = _key_arrays(build, key_cols)
    live = build.row_mask & kvalid
    return ([jnp.where(live, op, _key_sentinel(op.dtype)) for op in ops],
            live, jnp.arange(build.capacity, dtype=jnp.int32))


def prepare_build(build: Batch, key_cols: Sequence[int]):
    """One-time build-side preparation (sorted key operands + live mask +
    permutation) shared by every probe batch of a join — the role of the
    reference's LookupSource, built once by HashBuilderOperator and probed
    by many LookupJoinOperators. Pure arrays (a pytree), so it crosses
    jit boundaries and can be computed once per build under jit."""
    return build_sorted(build, key_cols)


def prepare_direct(build: Batch, key_cols: Sequence[int], lo0,
                   size: int, unique: bool = False):
    """Direct-address lookup table for a single integer key with a
    host-known bounded range — the BigintGroupByHash-style dense-int
    fast path applied to joins (reference BigintGroupByHash.java's array
    mode; PagesHash replaced by addressing).

    TPU rationale: random gathers run at ~55M/s on v5e, and the sorted
    path's binary search spends O(log n) gathers per probe row; a direct
    table answers a probe key's first match in ONE gather (lo_table) and
    the [lo, hi) of its sorted match run in two, independent of build
    size.

    Returns (lo0, lo_table, cnt_table, s_ops, slive, perm): tables are
    indexed by (key - lo0); empty slots hold (n, 0). INVARIANT (both
    direct layouts; ``_point_lookup`` rests on it and reads lo_table
    alone): dead rows go to the overflow slot, which is sliced off, so
    for every slot inside the table ``lo < n`` iff ``cnt > 0``.
    ``unique``: the caller knows every key once; the build is addressed
    as it stands (``build_in_order``)."""
    s_ops, slive, perm = (build_in_order if unique
                          else build_sorted)(build, key_cols)
    n = s_ops[0].shape[0]
    off = jnp.clip(s_ops[0] - lo0, 0, size - 1).astype(jnp.int32)
    tgt = jnp.where(slive, off, size)       # dead rows -> overflow slot
    idx = jnp.arange(n, dtype=jnp.int32)
    lo_table = jnp.full(size + 1, n, dtype=jnp.int32) \
        .at[tgt].min(idx)[:size]
    cnt_table = _run_lengths(lo_table, tgt, n, size, unique)
    return (jnp.asarray(lo0, dtype=jnp.int64), lo_table, cnt_table,
            s_ops, slive, perm)


def _run_lengths(lo_table, tgt, n: int, size: int, unique: bool):
    """A direct layout's cnt_table: the rows of each slot's run, a
    second scatter over the build; where every key stands once, 1 where
    the slot is taken (the invariant), with no scatter."""
    if unique:
        return (lo_table < n).astype(jnp.int32)
    return jnp.zeros(size + 1, dtype=jnp.int32) \
        .at[tgt].add(jnp.int32(1))[:size]


#: largest composite slot-table size a planner-keyed direct build may
#: allocate (slots x 2 x i32 = 512MB of HBM at the cap); the planner
#: gate (optimizer._attach_join_strategy) and the executor both respect
#: it, so key_bounds on a JoinNode always fit
DIRECT_KEYED_LIMIT = 1 << 26


def direct_keyed_plan(key_bounds, limit: int = DIRECT_KEYED_LIMIT):
    """Host-static (los, sizes, K) for a planner-bounded multi-key
    direct-address table, or None when it cannot engage: every key needs
    a hard [lo, hi] and the mixed-radix composite product must stay
    under ``limit`` — the join-side mirror of
    ``ops/aggregation.dense_group_plan``'s dispatch rule."""
    if not key_bounds or any(b is None for b in key_bounds):
        return None
    los: List[int] = []
    sizes: List[int] = []
    K = 1
    for lo, hi in key_bounds:
        if hi < lo:
            return None
        span = int(hi) - int(lo) + 1
        los.append(int(lo))
        sizes.append(span)
        K *= span
        if K > limit:
            return None
    return tuple(los), tuple(sizes), K


def _composite_code(ops: Sequence[jnp.ndarray], los, sizes):
    """(code, in_domain) of key-operand tuples against per-key
    [lo, lo+size) domains: code is the mixed-radix slot index — the same
    composite i32 code ``dense_group_plan`` builds for GROUP BY, minus
    the NULL component (null keys never match a join). ``los``/``sizes``
    index positionally (host tuples or traced i64 arrays both work)."""
    code = jnp.zeros(ops[0].shape, dtype=jnp.int64)
    ind = jnp.ones(ops[0].shape, dtype=bool)
    for i, op in enumerate(ops):
        lo = los[i]
        size = sizes[i]
        off = op.astype(jnp.int64) - lo
        ind = ind & (off >= 0) & (off < size)
        code = code * size + jnp.clip(off, 0, size - 1)
    return code, ind


def prepare_direct_keyed(build: Batch, key_cols: Sequence[int],
                         los: Sequence[int], sizes: Sequence[int],
                         size: int, unique: bool = False):
    """Multi-key direct-address table from PLANNER-PROMISED key bounds
    (``JoinNode.key_bounds``): composite mixed-radix slot per key tuple,
    answered in one gather per probe lane (two for a run's length)
    regardless of arity or build size; ``prepare_direct``'s invariant
    (``lo < n`` iff ``cnt > 0``) holds here too. Table capacity is
    host-known at PLAN time, so every batch of every query sharing the
    plan reuses one executable shape.

    Live build keys outside their promised bounds land in the overflow
    slot (they can never match) — the executor independently raises
    STATS_BOUND_VIOLATION for such rows through the row-error channel
    (the ``dense_group_plan`` contract), so an overclaiming connector
    fails the query instead of silently dropping matches.

    ``unique``: as for ``prepare_direct``.

    Returns (los, sizes, lo_table, cnt_table, s_ops, slive, perm)."""
    s_ops, slive, perm = (build_in_order if unique
                          else build_sorted)(build, key_cols)
    n = s_ops[0].shape[0]
    code, inr = _composite_code(s_ops, los, sizes)
    # lexicographic sort == composite-code sort inside the domain, so
    # equal-tuple runs are contiguous and [lo, lo+cnt) is exact
    tgt = jnp.where(slive & inr, code, size).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    lo_table = jnp.full(size + 1, n, dtype=jnp.int32) \
        .at[tgt].min(idx)[:size]
    cnt_table = _run_lengths(lo_table, tgt, n, size, unique)
    return (jnp.asarray(los, dtype=jnp.int64),
            jnp.asarray(sizes, dtype=jnp.int64),
            lo_table, cnt_table, s_ops, slive, perm)


def _is_direct(prepared) -> bool:
    return prepared is not None and len(prepared) == 6


def _is_direct_keyed(prepared) -> bool:
    return prepared is not None and len(prepared) == 7


def is_direct_prepared(prepared) -> bool:
    """Either direct layout (single-key measured or multi-key planner
    bounds) — the dispatch the executors report as strategy=direct."""
    return _is_direct(prepared) or _is_direct_keyed(prepared)


def _split_prepared(prepared):
    if _is_direct(prepared):
        return prepared[3], prepared[4], prepared[5]
    if _is_direct_keyed(prepared):
        return prepared[4], prepared[5], prepared[6]
    return prepared


def direct_slot_codes(q_ops, prepared):
    """(slot, in_domain) probe-side addressing of a direct prepared —
    slot is a clipped i32 index into the lookup tables. Shared by the
    XLA probe path and the Pallas probe kernel so the two stay
    row-exact by construction."""
    if _is_direct(prepared):
        lo0, lo_table = prepared[0], prepared[1]
        size = lo_table.shape[0]
        off = q_ops[0] - lo0
        inr = (off >= 0) & (off < size)
        return jnp.clip(off, 0, size - 1).astype(jnp.int32), inr
    los, sizes, lo_table = prepared[0], prepared[1], prepared[2]
    size = lo_table.shape[0]
    code, inr = _composite_code(q_ops, los, sizes)
    return jnp.clip(code, 0, size - 1).astype(jnp.int32), inr


#: largest capacity of a sorted build that a probe answers by comparing
#: every lane with every build key, no gather at all (a static shape:
#: ``_point_lookup`` and ``_range_lookup`` read it here, and
#: ``exec/local._prepare_join_build`` hands builds at or under it the
#: sorted layout instead of filling a direct table for them). The
#: largest power of two MEASURED at which the membership is at least
#: twice as fast as ONE gather into a direct table, one key and a
#: two-key tuple alike (`tools/join_probe.py`, TPU v5 lite, PR 34; ns a
#: probe lane at 2^20 and 2^21 lanes, the same within 2 %): one gather
#: 7.2 (two, the parent's: 21.1 to 22.0; table of 2^17 or 2^24 slots
#: alike); compare-all, the hit alone, one key / two keys: n = 128
#: 0.22 to 0.25 / 0.38 to 0.39, 512 0.80 to 0.82 / 1.40 to 1.42, 2048
#: 3.15 to 3.17 / 5.51 to 5.52 (two keys: not twice as fast), 8192 12.5
#: / 21.9; with the position too (a lookup join's): 128 0.48 / 0.88 to
#: 1.20, 512 1.76 to 1.85 / 3.36 to 4.60, 2048 6.9 to 7.3 / 13.3 to
#: 18.2 (slower than the gather). The binary search it replaces there:
#: 144 to 448 ns a lane
COMPARE_ALL_LIMIT = 512

#: build keys compared in one pass of ``_compare_all``'s loop: a
#: [chunk, lanes] compare reduces over its MAJOR axis inside one fusion
#: (lanes stay on the vector lanes), and a longer build loops over
#: chunks so that no [n, lanes] buffer exists whatever XLA fuses
_COMPARE_CHUNK = 128


def lookup_form(prepared) -> str:
    """How a probe lane finds its first match in ``prepared``: ``direct``
    (one gather into a table), ``compare`` (a sorted build at or under
    COMPARE_ALL_LIMIT: no gather) or ``sorted`` (binary search) — the
    label the executors report as the join's strategy."""
    if is_direct_prepared(prepared):
        return "direct"
    n = prepared[0][0].shape[0]
    return "compare" if n <= COMPARE_ALL_LIMIT else "sorted"


def _compare_all(s_ops, slive, q_ops):
    """(lo, cnt) per probe lane over a SMALL sorted build, by comparing
    each lane with every build key: lo counts the build tuples
    lexicographically below the lane's (the keys are sorted and dead
    rows hold the max sentinel, so that is the first match's position,
    what ``_lex_searchsorted(side="left")`` finds), cnt the LIVE tuples
    equal to it (``slive`` keeps a probe key equal to the sentinel from
    matching dead rows). Elementwise compares and a reduction over the
    build axis: no gather, no memory access beyond the operands."""
    n = s_ops[0].shape[0]
    shape = q_ops[0].shape

    def chunk(s_chunk, live_chunk):
        less = jnp.zeros((live_chunk.shape[0],) + shape, dtype=bool)
        eq = jnp.ones_like(less)
        for s, q in zip(s_chunk, q_ops):
            sv = s[:, None]
            less = less | (eq & (sv < q[None, :]))
            eq = eq & (sv == q[None, :])
        return (jnp.sum(less, axis=0, dtype=jnp.int32),
                jnp.sum(eq & live_chunk[:, None], axis=0, dtype=jnp.int32))

    if n <= _COMPARE_CHUNK:
        return chunk(s_ops, slive)
    rows = -(-n // _COMPARE_CHUNK)
    pad = rows * _COMPARE_CHUNK - n     # 0 for the buckets' powers of two
    # padding counts nowhere: the max sentinel is below no key, and dead
    s2 = [jnp.pad(s, (0, pad), constant_values=_key_sentinel(s.dtype))
          .reshape(rows, _COMPARE_CHUNK) for s in s_ops]
    live2 = jnp.pad(slive, (0, pad)).reshape(rows, _COMPARE_CHUNK)

    def body(i, acc):
        lo, cnt = chunk([s[i] for s in s2], live2[i])
        return acc[0] + lo, acc[1] + cnt
    zero = jnp.zeros(shape, dtype=jnp.int32)
    return jax.lax.fori_loop(0, rows, body, (zero, zero))


def _range_lookup(q_ops, prepared):
    """Per-probe-lane [lo, hi) over the SORTED build — via the direct
    table (2 gathers, single-key or composite), by comparing with every
    key of a small build (no gather) or composite binary search
    (2 log n gathers)."""
    if is_direct_prepared(prepared):
        s_ops = _split_prepared(prepared)[0]
        lo_table, cnt_table = ((prepared[1], prepared[2])
                               if _is_direct(prepared)
                               else (prepared[2], prepared[3]))
        n = s_ops[0].shape[0]
        idx, inr = direct_slot_codes(q_ops, prepared)
        lo = jnp.where(inr, jnp.take(lo_table, idx, axis=0), n)
        cnt = jnp.where(inr, jnp.take(cnt_table, idx, axis=0), 0)
        return lo.astype(jnp.int32), (lo + cnt).astype(jnp.int32)
    s_ops, slive, _ = prepared
    if lookup_form(prepared) == "compare":
        lo, cnt = _compare_all(s_ops, slive, q_ops)
        return lo, lo + cnt
    lo = _lex_searchsorted(s_ops, q_ops, side="left")
    hi = _lex_searchsorted(s_ops, q_ops, side="right")
    return lo, hi


def _point_lookup(q_ops, prepared):
    """(pos, hit) of each probe lane's first match in the sorted build:
    ONE gather into a direct table (``lo < n`` says the slot is taken:
    ``prepare_direct``'s invariant, so cnt_table is not read), none for
    a small sorted build, a binary search otherwise."""
    s_ops, slive, _ = _split_prepared(prepared)
    n = s_ops[0].shape[0]
    if is_direct_prepared(prepared):
        lo_table = prepared[1] if _is_direct(prepared) else prepared[2]
        idx, inr = direct_slot_codes(q_ops, prepared)
        lo = jnp.take(lo_table, idx, axis=0)
        return jnp.clip(lo, 0, n - 1), inr & (lo < n)
    if lookup_form(prepared) == "compare":
        lo, cnt = _compare_all(s_ops, slive, q_ops)
        return jnp.clip(lo, 0, n - 1), cnt > 0
    pos = _lex_searchsorted(s_ops, q_ops, side="left")
    pos = jnp.minimum(pos, n - 1)
    hit = _tuple_eq(s_ops, q_ops, pos) & jnp.take(slive, pos, axis=0)
    return pos, hit


def _lex_searchsorted(s_ops: Sequence[jnp.ndarray],
                      q_ops: Sequence[jnp.ndarray],
                      side: str) -> jnp.ndarray:
    """Vectorized binary search of query tuples in lexicographically
    sorted operand arrays — searchsorted generalized to composite keys.
    O(log n) gathers per key column."""
    n = s_ops[0].shape[0]
    lo = jnp.zeros(q_ops[0].shape, dtype=jnp.int32)
    hi = jnp.full_like(lo, n)

    def go_right(mid):
        # side=left:  s[mid] <  q   |   side=right:  s[mid] <= q
        less = jnp.zeros(mid.shape, dtype=bool)
        eq = jnp.ones(mid.shape, dtype=bool)
        for s, q in zip(s_ops, q_ops):
            sv = jnp.take(s, mid, axis=0)
            less = less | (eq & (sv < q))
            eq = eq & (sv == q)
        return (less | eq) if side == "right" else less

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        r = go_right(mid)
        return (jnp.where(r, mid + 1, lo), jnp.where(r, hi, mid))

    lo, hi = jax.lax.fori_loop(0, max(n.bit_length(), 1), body, (lo, hi))
    return lo


def _tuple_eq(s_ops, q_ops, pos) -> jnp.ndarray:
    eq = jnp.ones(pos.shape, dtype=bool)
    for s, q in zip(s_ops, q_ops):
        eq = eq & (jnp.take(s, pos, axis=0) == q)
    return eq


def payload_form(probe_lanes: int, build_lanes: int, n_payload: int) -> str:
    """How a probe program reads the payload columns of a build whose
    prepared layout addresses it through a permutation, from the three
    static sizes of the call: ``composed`` gathers the permutation at the
    matched positions (ONE int32 gather a probe lane) and then each
    column's data and validity from the build AS IT STANDS; ``permuted``
    first gathers each column's data and validity through the whole
    permutation (two gathers a BUILD lane a column) and reads the sorted
    copies at the positions. Both read the same rows; the cheaper one is
    the one with fewer gathers: ``composed`` where one more gather a
    probe lane is fewer than two a build lane a column.

    MEASURED (`tools/join_probe.py payload`, TPU v5 lite, PR 36; ms a
    call, permuted / composed, BIGINT columns): TPC-H Q3's shape, 2^15
    lanes against 2^21 x four columns, 233.58 / 3.35 (against 2^23:
    927.70 / 3.62; one column: 50.68 / 1.03); 2^18 against 2^21 x four
    263.66 / 28.09; 2^20 against 2^21 x four 387.52 / 121.19. A gather
    costs 10 to 14 ns a lane in either form (`composed` 31 ns a probe
    lane with one column, 100 to 116 with four), so the crossover IS
    at equal gather counts: AT it the two are within 3 to 10 % (2^20
    against 2^17 x four 154.59 / 150.09; 2^18 against 2^17 x one 10.61
    / 9.54), a factor of four past it `permuted` leads by 8 % (2^20
    against 2^17 x one 34.99 / 38.09), a factor of four before it
    `composed` by 22 % (2^18 against 2^17 x four 48.49 / 37.59).

    The ONE statement of the rule: ``read_payload`` (the traced helper)
    and the executor's counter ``join_payload_selected_total.<form>``
    both call it."""
    if probe_lanes < 2 * n_payload * build_lanes:
        return "composed"
    return "permuted"


def permuted_payload(build: Batch, payload: Sequence[int], perm
                     ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """(data, validity) of each payload column in the prepared layout's
    SORTED order: two gathers of the BUILD's lanes a column. The one
    place a probe program permutes a build: ``read_payload``'s
    ``permuted`` form, and the Pallas probe (``ops/pallas_join``), whose
    kernel gathers inside VMEM from planes that have to be sorted."""
    return [(jnp.take(build.columns[ci].data, perm, axis=0),
             jnp.take(build.columns[ci].validity, perm, axis=0))
            for ci in payload]


def read_payload(build: Batch, payload: Sequence[int], perm, pos
                 ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """(data, validity) of each payload column at the sorted positions
    ``pos`` (any shape: ``[C]`` of a lookup, ``[k, C]`` of an
    expansion), in the form ``payload_form`` picks from the static
    shapes; the rows read are the same in both."""
    if payload_form(pos.size, build.capacity, len(payload)) == "composed":
        orig = jnp.take(perm, pos, axis=0)
        return [(jnp.take(build.columns[ci].data, orig, axis=0),
                 jnp.take(build.columns[ci].validity, orig, axis=0))
                for ci in payload]
    return [(jnp.take(sdata, pos, axis=0), jnp.take(svalid, pos, axis=0))
            for sdata, svalid in permuted_payload(build, payload, perm)]


def lookup_join(
    probe: Batch,
    build: Batch,
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    payload: Sequence[int],
    payload_names: Sequence[str],
    join_type: str = "inner",
    prepared=None,
) -> Batch:
    """Join probe against unique-key build side.

    join_type: 'inner' | 'left' (probe-preserving).
    Output schema = probe columns + named build payload columns.
    ``prepared`` (from prepare_build) skips re-sorting the build side.
    """
    assert join_type in ("inner", "left")
    prepared = prepared or build_sorted(build, build_keys)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    pos, hit = _point_lookup(q_ops, prepared)
    match = probe.row_mask & pvalid & hit

    out_fields = list(zip(probe.schema.names, probe.schema.types))
    out_cols: List[Column] = list(probe.columns)
    for ci, name, (data, valid) in zip(
            payload, payload_names, read_payload(build, payload, perm, pos)):
        c = build.columns[ci]
        out_fields.append((name, c.type))
        out_cols.append(Column(c.type, data, valid & match, c.dictionary))
    if join_type == "inner":
        mask = match
    else:
        mask = probe.row_mask
    return Batch(Schema(out_fields), out_cols, mask)


# -- a unique build's payload, for a residual decided on the one match ---------

def _payload_words(c: Column) -> List[jnp.ndarray]:
    """A column's data as uint32 words (low word first), lane by lane."""
    d = c.data
    if getattr(d, "ndim", 1) == 2:          # long decimal: two int64 limbs
        parts = [d[..., 0], d[..., 1]]
    elif d.dtype == jnp.bool_:
        parts = [d.astype(jnp.uint32)]
    else:
        parts = [d]
    out: List[jnp.ndarray] = []
    for x in parts:
        if x.dtype.itemsize == 8:
            u = jax.lax.bitcast_convert_type(x, jnp.uint64)
            out += [u.astype(jnp.uint32),
                    (u >> jnp.uint64(32)).astype(jnp.uint32)]
        elif x.dtype.itemsize == 4:
            out.append(jax.lax.bitcast_convert_type(x, jnp.uint32))
        else:
            out.append(x.astype(jnp.int32).astype(jnp.uint32))
    return out


def _payload_width(c: Column) -> int:
    """Words ``_payload_words`` makes of the column."""
    limbs = 2 if getattr(c.data, "ndim", 1) == 2 else 1
    return limbs * (2 if c.data.dtype.itemsize == 8 else 1)


def _payload_column(like: Column, words: List[jnp.ndarray],
                    valid: jnp.ndarray) -> Column:
    """The inverse of ``_payload_words`` for gathered words."""
    def one(dtype, ws):
        if dtype.itemsize == 8:
            u = ws[0].astype(jnp.uint64) | (ws[1].astype(jnp.uint64)
                                            << jnp.uint64(32))
            return jax.lax.bitcast_convert_type(u, dtype)
        if dtype.itemsize == 4:
            return jax.lax.bitcast_convert_type(ws[0], dtype)
        if dtype == jnp.bool_:
            return ws[0] != 0
        return ws[0].astype(jnp.int32).astype(dtype)
    d = like.data
    if getattr(d, "ndim", 1) == 2:
        data = jnp.stack([one(d.dtype, words[0:2]), one(d.dtype, words[2:4])],
                         axis=-1)
    else:
        data = one(d.dtype, words)
    return Column(like.type, data, valid, like.dictionary)


def pack_sorted_payload(build: Batch, payload: Sequence[int], prepared,
                        in_order: bool = False):
    """The ``payload`` columns of a build in the prepared layout's SORTED
    order, packed into ONE uint32 array of shape [words, n]: every
    column's data words and, last, one word of validity bits (bit j:
    column j). Made once a build, so that a probe lane reads all it
    needs of its match in one gather of a column of words at the
    position ``_point_lookup`` found, where reading each column's data
    and validity through ``perm`` is four gathers a column a lane
    (``lookup_join``, PERF.md section 5: ~7 to 11 ns each).
    ``in_order``: the layout is the build as it stands
    (``build_in_order``), and nothing is gathered here either."""
    assert len(payload) <= 32
    words: List[jnp.ndarray] = []
    bits = jnp.zeros(build.capacity, dtype=jnp.uint32)
    for j, ci in enumerate(payload):
        c = build.columns[ci]
        words += _payload_words(c)
        bits = bits | (c.validity.astype(jnp.uint32) << jnp.uint32(j))
    packed = jnp.stack(words + [bits])
    if in_order:
        return packed
    return jnp.take(packed, _split_prepared(prepared)[2], axis=1)


def keyed_match(probe: Batch, build: Batch, probe_keys: Sequence[int],
                payload: Sequence[int], prepared, packed
                ) -> Tuple[List[Column], jnp.ndarray]:
    """(payload columns, match) per probe lane against a build that
    holds every key ONCE: the lane's one match found by
    ``_point_lookup`` (one gather into a direct table, none for a small
    build), its payload read from ``packed`` (``pack_sorted_payload``)
    in one more. Nothing is expanded: the output has the probe's
    capacity. NULL keys never match; a payload column is NULL where
    there is no match."""
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    pos, hit = _point_lookup(q_ops, prepared)
    match = probe.row_mask & pvalid & hit
    got = jnp.take(packed, pos, axis=1)             # [words, lanes]
    cols: List[Column] = []
    at = 0
    for j, ci in enumerate(payload):
        c = build.columns[ci]
        n = _payload_width(c)
        valid = ((got[-1] >> jnp.uint32(j)) & jnp.uint32(1)) != 0
        cols.append(_payload_column(c, [got[at + i] for i in range(n)],
                                    valid & match))
        at += n
    return cols, match


def match_count_max(
    probe: Batch, build: Batch,
    probe_keys: Sequence[int], build_keys: Sequence[int],
    prepared=None,
) -> jnp.ndarray:
    """Max build matches for any live probe key (device scalar).

    The skew fallback: for non-skewed builds the executor sizes
    ``expand_join`` from the probe-independent ``max_multiplicity`` bound
    (one readback per build); when that bound exceeds SKEW_MATCH_LIMIT it
    syncs this per (probe, build) pair instead, so only probe batches
    that actually hit the hot key pay the chunked skew loop — the
    capacity analogue of Presto's PositionLinks chain length (reference
    operator/ArrayPositionLinks.java).
    """
    prepared = prepared or build_sorted(build, build_keys)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    live = probe.row_mask & pvalid
    # live build rows sort before the dead-sentinel tail, so [lo, hi)
    # spans only live matches
    lo, hi = _range_lookup(q_ops, prepared)
    cnt = jnp.where(live, hi - lo, 0)
    return jnp.max(cnt) if cnt.shape[0] else jnp.asarray(0)


def max_multiplicity(prepared) -> jnp.ndarray:
    """Max live-key multiplicity of a PREPARED build side (device scalar).

    A probe-independent upper bound on ``match_count_max`` for EVERY probe
    batch: no probe key can match more build rows than the most frequent
    build key has. The executor reads this back ONCE per build and reuses
    it as the static expansion factor for all probe batches — replacing a
    per-probe-batch ``match_count_max`` sync (each a host stall).
    Mirrors the reference's build-side PositionLinks, whose chain lengths
    are likewise a property of the build alone (reference
    operator/ArrayPositionLinks.java).
    """
    if is_direct_prepared(prepared):
        cnt_table = prepared[2] if _is_direct(prepared) else prepared[3]
        if cnt_table.shape[0] == 0:
            return jnp.asarray(0, dtype=jnp.int64)
        # keyed tables route bound-violating build rows to the overflow
        # slot, so the table max alone would undercount a (failing)
        # query's multiplicity — but such queries die on the error
        # channel before any expansion sizing matters
        return jnp.max(cnt_table).astype(jnp.int64)
    s_ops, slive, _ = prepared
    n = s_ops[0].shape[0]
    if n == 0:
        return jnp.asarray(0, dtype=jnp.int64)
    idx = jnp.arange(n, dtype=jnp.int64)
    diff = jnp.zeros(n, dtype=bool).at[0].set(True)
    for op in s_ops:
        diff = diff | (op != jnp.roll(op, 1))
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(diff, idx, -1))
    # dead rows share one sentinel run; exclude them via slive
    return jnp.max(jnp.where(slive, idx - start + 1, 0))


def expand_join(
    probe: Batch,
    build: Batch,
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    payload: Sequence[int],
    payload_names: Sequence[str],
    join_type: str = "inner",
    max_matches: int = 1,
    prepared=None,
) -> Batch:
    """Many-to-many equi-join with static expansion factor.

    Output capacity = probe capacity * max_matches: slot k of probe row i
    holds its k-th match (masked off past the row's match count). The
    caller obtains ``max_matches`` from ``match_count_max`` (bucketed, so
    kernels recompile only when the multiplicity crosses a power of two).
    Left joins keep unmatched probe rows in slot 0 with null payload.
    """
    assert join_type in ("inner", "left")
    k = max(1, max_matches)
    prepared = prepared or build_sorted(build, build_keys)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    live = probe.row_mask & pvalid
    lo, hi = _range_lookup(q_ops, prepared)
    cnt = jnp.where(live, hi - lo, 0)

    # [k, C] grids -> flattened [k*C] output (probe-major within slots)
    slot = jnp.arange(k)[:, None]                      # [k, 1]
    pos = jnp.minimum(lo[None, :] + slot, s_ops[0].shape[0] - 1)
    # slive guards the sentinel edge (a probe key equal to int64-max would
    # otherwise "match" dead build rows)
    matched = (slot < cnt[None, :]) & jnp.take(slive, pos, axis=0)  # [k, C]

    out_fields = list(zip(probe.schema.names, probe.schema.types))
    out_cols: List[Column] = []

    def flat(x):
        # [k, C, ...] -> [k * C, ...]: a long decimal keeps its limbs
        return x.reshape((-1,) + x.shape[2:])

    for c in probe.columns:
        data = jnp.broadcast_to(c.data[None], (k,) + c.data.shape)
        valid = jnp.broadcast_to(c.validity[None, :], (k,) + c.validity.shape)
        out_cols.append(Column(c.type, flat(data), valid.reshape(-1),
                               c.dictionary))
    for ci, name, (gdata, gvalid) in zip(
            payload, payload_names, read_payload(build, payload, perm, pos)):
        c = build.columns[ci]
        gvalid = gvalid & matched                      # [k, C]
        out_fields.append((name, c.type))
        out_cols.append(Column(c.type, flat(gdata), gvalid.reshape(-1),
                               c.dictionary))
    if join_type == "inner":
        mask = matched
    else:
        # unmatched probe rows survive in slot 0 with null payload
        first_slot = (slot == 0) & (cnt[None, :] == 0) & probe.row_mask
        mask = matched | first_slot
    return Batch(Schema(out_fields), out_cols, mask.reshape(-1))


def build_key_ranks(build: Batch, key_cols: Sequence[int],
                    prepared=None) -> jnp.ndarray:
    """0-based occurrence rank of each build row within its key tuple, in
    ORIGINAL row order (dead/null-key rows get 0). The executor uses this
    to slice a skewed build side into bounded-multiplicity chunks instead
    of letting expand_join's probe_capacity x max_matches output explode
    (the role of reference PositionLinks chains, which walk matches
    incrementally instead of materializing them)."""
    s_ops, slive, perm = _split_prepared(
        prepared or build_sorted(build, key_cols))
    n = s_ops[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    diff = jnp.zeros(n, dtype=bool).at[0].set(True)
    for op in s_ops:
        diff = diff | (op != jnp.roll(op, 1))
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(diff, idx, -1))
    rank_sorted = jnp.where(slive, idx - start, 0)
    return jnp.zeros(n, dtype=jnp.int64).at[perm].set(rank_sorted)


def build_match_mask(
    probe: Batch, build: Batch,
    probe_keys: Sequence[int], build_keys: Sequence[int],
    prepared=None,
) -> jnp.ndarray:
    """bool[build.capacity] in ORIGINAL build order: which build rows have
    at least one live match in this probe batch. The executor ORs these
    across probe batches to emit the unmatched-build tail of a FULL OUTER
    join (the role of reference LookupJoinOperator's OuterPositionTracker /
    LookupOuterOperator visited-positions bitmap)."""
    prepared = prepared or build_sorted(build, build_keys)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    live = probe.row_mask & pvalid
    lo, hi = _range_lookup(q_ops, prepared)
    n = s_ops[0].shape[0]
    # difference-array coverage of all [lo, hi) ranges: two scatters +
    # one scan instead of a per-match scatter
    inc = live.astype(jnp.int32)
    add = (jnp.zeros(n + 1, dtype=jnp.int32)
           .at[jnp.where(live, lo, n)].add(inc)
           .at[jnp.where(live, hi, n)].add(-inc))
    covered = (prefix_sum(add[:n]) > 0) & slive
    return jnp.zeros(n, dtype=bool).at[perm].set(covered)


def semi_join_mask(
    probe: Batch,
    build: Batch,
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    negated: bool = False,
    null_aware: bool = True,
    prepared=None,
) -> jnp.ndarray:
    """Membership mask for semi/anti-joins (IN / NOT IN / [NOT] EXISTS;
    reference HashSemiJoinOperator.java + SetBuilderOperator.java).

    null_aware=True (IN / NOT IN) follows ANSI IN-predicate semantics: a
    NULL probe key never matches; for NOT IN, any NULL build key makes
    membership UNKNOWN for non-matching rows (nothing passes), while an
    EMPTY build set makes NOT IN vacuously TRUE for every probe row —
    including NULL keys. null_aware=False (decorrelated [NOT] EXISTS)
    treats NULL keys as simply never equal: NOT EXISTS keeps every probe
    row without a live match.
    """
    prepared = prepared or build_sorted(build, build_keys)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    pos, hit = _point_lookup(q_ops, prepared)
    if not negated:
        return probe.row_mask & pvalid & hit
    if not null_aware:
        return probe.row_mask & ~(pvalid & hit)
    _bops, bvalid = _key_arrays(build, build_keys)
    build_has_null = jnp.any(build.row_mask & ~bvalid)
    build_empty = ~jnp.any(build.row_mask)
    anti = probe.row_mask & pvalid & ~hit & ~build_has_null
    return jnp.where(build_empty, probe.row_mask, anti)


def unique_match_build_mask(
    probe: Batch, build: Batch,
    probe_keys: Sequence[int], build_keys: Sequence[int],
    survived: jnp.ndarray,
    prepared=None,
) -> jnp.ndarray:
    """bool[build.capacity] in ORIGINAL build order: build rows whose
    unique-key match in this probe batch SURVIVED a residual predicate —
    the FULL OUTER visited-positions bitmap with a join filter applied
    (reference LookupJoinOperator's OuterPositionTracker +
    JoinFilterFunctionCompiler: a filtered-out match must not mark the
    build row as matched)."""
    prepared = prepared or build_sorted(build, build_keys)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    pos, hit = _point_lookup(q_ops, prepared)
    ok = survived & hit & probe.row_mask & pvalid
    orig = jnp.take(perm, pos, axis=0)
    n = s_ops[0].shape[0]
    return jnp.zeros(n, dtype=bool).at[
        jnp.where(ok, orig, n)].max(ok, mode="drop")


def expand_match_origins(
    probe: Batch, build: Batch,
    probe_keys: Sequence[int], build_keys: Sequence[int],
    max_matches: int,
    prepared=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(orig_build_row, matched) per expand_join output lane, flattened
    [k * probe.capacity] in the same lane order as expand_join — lets a
    residual-filtered FULL OUTER join scatter surviving lanes back onto
    original build rows for the unmatched-tail bitmap."""
    k = max(1, max_matches)
    prepared = prepared or build_sorted(build, build_keys)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    live = probe.row_mask & pvalid
    lo, hi = _range_lookup(q_ops, prepared)
    cnt = jnp.where(live, hi - lo, 0)
    slot = jnp.arange(k)[:, None]
    pos = jnp.minimum(lo[None, :] + slot, s_ops[0].shape[0] - 1)
    matched = (slot < cnt[None, :]) & jnp.take(slive, pos, axis=0)
    orig = jnp.take(perm, pos, axis=0)
    return orig.reshape(-1), matched.reshape(-1)
