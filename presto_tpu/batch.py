"""Columnar data plane: device-resident batches with static padded shapes.

Conceptual parity with Presto's Page/Block (reference
presto-spi/src/main/java/io/prestosql/spi/Page.java:39-62 and
presto-spi/src/main/java/io/prestosql/spi/block/Block.java:23), re-designed
for XLA:

- A Batch is a struct-of-arrays: one flat jnp array per column, padded to a
  static *capacity* (power-of-two bucket) so kernels compile once per bucket
  and never see dynamic shapes.
- Liveness is a boolean ``row_mask`` (True = live row). Filters produce masks
  instead of compacting, which keeps everything branch-free on the VPU;
  explicit ``compact()`` exists for when gathers pay off.
- Nulls are per-column validity masks (Presto's per-Block isNull arrays).
- Strings are dictionary codes (int32) + a host-side vocabulary per column
  (Presto's DictionaryBlock made mandatory for device residency).

Batch and Column are registered as JAX pytrees, so jitted operator kernels
take and return them directly; the schema/dictionaries ride in the static
treedef, which is exactly the "compile once per (schema, bucket)" contract of
Presto's compiled PageProcessor (reference
presto-main/.../sql/gen/PageFunctionCompiler.java:121-136 cache keys).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .obs.metrics import REGISTRY
from .obs.trace import device_sync
from .types import ArrayType, MapType, Type, VarcharType, CharType, parse_type


def bucket_capacity(n: int, minimum: int = 128) -> int:
    """Round row count up to a power-of-two bucket (recompile avoidance).

    Mirrors PageProcessor's adaptive batching buckets (reference
    presto-main/.../operator/project/PageProcessor.java:56 MAX_BATCH_SIZE).
    """
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    type: Type


class Schema:
    """Ordered, named, typed columns."""

    def __init__(self, fields: Sequence[Tuple[str, Type]]):
        self.fields: Tuple[Field, ...] = tuple(
            f if isinstance(f, Field) else Field(f[0], f[1]) for f in fields
        )
        self._index = {f.name: i for i, f in enumerate(self.fields)}

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    @property
    def types(self) -> List[Type]:
        return [f.type for f in self.fields]

    def index_of(self, name: str) -> int:
        return self._index[name]

    def type_of(self, name: str) -> Type:
        return self.fields[self._index[name]].type

    def __len__(self) -> int:
        return len(self.fields)

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def __eq__(self, other) -> bool:
        return isinstance(other, Schema) and self.fields == other.fields

    def __hash__(self) -> int:
        return hash(self.fields)

    def __repr__(self) -> str:
        inner = ", ".join(f"{f.name} {f.type.display()}" for f in self.fields)
        return f"Schema({inner})"

    def select(self, names: Sequence[str]) -> "Schema":
        return Schema([(n, self.type_of(n)) for n in names])


class Column:
    """One device column: data + validity, plus host dictionary for strings."""

    def __init__(
        self,
        type: Type,
        data: jax.Array,
        validity: jax.Array,
        dictionary: Optional[Tuple[str, ...]] = None,
    ):
        self.type = type
        self.data = data
        self.validity = validity
        self.dictionary = dictionary

    @property
    def capacity(self) -> int:
        # validity is always the row-level [capacity] mask, even for
        # composite columns whose data is a tuple of arrays
        return self.validity.shape[0]

    def tree_flatten(self):
        # data may be a tuple of arrays (ARRAY/MAP/ROW columns): jax
        # recurses into nested containers automatically
        return (self.data, self.validity), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        type_, dictionary = aux
        data, validity = children
        return cls(type_, data, validity, dictionary)

    def __repr__(self) -> str:
        return f"Column({self.type.display()}, cap={self.data.shape})"


jax.tree_util.register_pytree_node(
    Column, Column.tree_flatten, Column.tree_unflatten
)


class Batch:
    """A horizontal slice of rows: aligned columns + row liveness mask."""

    def __init__(self, schema: Schema, columns: Sequence[Column], row_mask: jax.Array):
        self.schema = schema
        self.columns = tuple(columns)
        self.row_mask = row_mask

    # -- pytree protocol ----------------------------------------------------
    # Columns are themselves registered pytree nodes; let JAX recurse.
    def tree_flatten(self):
        return (self.columns, self.row_mask), self.schema

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, row_mask = children
        return cls(aux, columns, row_mask)

    # -- basic accessors ----------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.row_mask.shape[0])

    def count(self) -> jax.Array:
        """Number of live rows (device scalar)."""
        return jnp.sum(self.row_mask.astype(jnp.int32))

    def host_count(self, what: str = "host-count") -> int:
        # explicit device_get: an int() on a device scalar is an IMPLICIT
        # transfer, which jax.transfer_guard("disallow") rejects — sizing
        # syncs are deliberate and should read as such
        return int(device_sync(what, self.count()))

    def column(self, name: str) -> Column:
        return self.columns[self.schema.index_of(name)]

    def with_columns(self, schema: Schema, columns: Sequence[Column]) -> "Batch":
        return Batch(schema, columns, self.row_mask)

    def select(self, names: Sequence[str]) -> "Batch":
        cols = [self.column(n) for n in names]
        return Batch(self.schema.select(names), cols, self.row_mask)

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_arrays(
        schema: Schema,
        arrays: Sequence[np.ndarray],
        validity: Optional[Sequence[Optional[np.ndarray]]] = None,
        dictionaries: Optional[Sequence[Optional[Tuple[str, ...]]]] = None,
        capacity: Optional[int] = None,
        num_rows: Optional[int] = None,
    ) -> "Batch":
        """Build a device batch from host numpy arrays (already in storage repr)."""
        n = num_rows if num_rows is not None else (len(arrays[0]) if arrays else 0)
        cap = capacity or bucket_capacity(max(n, 1))
        cols = []
        for i, (f, arr) in enumerate(zip(schema.fields, arrays)):
            dt = f.type.storage_dtype
            width = getattr(f.type, "storage_width", None)
            shape = (cap,) if width is None else (cap, width)
            padded = np.zeros(shape, dtype=np.dtype(dt))
            padded[:n] = np.asarray(arr[:n]).astype(np.dtype(dt))
            if validity is not None and validity[i] is not None:
                v = np.zeros(cap, dtype=bool)
                v[:n] = validity[i][:n]
            else:
                v = np.zeros(cap, dtype=bool)
                v[:n] = True
            d = dictionaries[i] if dictionaries is not None else None
            cols.append(Column(f.type, jnp.asarray(padded), jnp.asarray(v), d))
        mask = np.zeros(cap, dtype=bool)
        mask[:n] = True
        return Batch(schema, cols, jnp.asarray(mask))

    @staticmethod
    def from_pydict(
        data: Dict[str, Tuple[Type, Sequence[Any]]], capacity: Optional[int] = None
    ) -> "Batch":
        """Build from python values: {name: (type, [values... (None = null)])}."""
        names = list(data.keys())
        schema_fields = []
        arrays: List[np.ndarray] = []
        validities: List[Optional[np.ndarray]] = []
        dictionaries: List[Optional[Tuple[str, ...]]] = []
        composite: Dict[int, Tuple[Type, List[Any]]] = {}
        n = None
        for name in names:
            typ, values = data[name]
            values = list(values)
            if n is None:
                n = len(values)
            elif len(values) != n:
                raise ValueError(
                    f"column {name!r} has {len(values)} values, expected {n}"
                )
            schema_fields.append((name, typ))
            if isinstance(typ, ArrayType):
                composite[len(schema_fields) - 1] = (typ, values)
                arrays.append(np.zeros(n, dtype=np.int32))   # placeholder
                validities.append(None)
                dictionaries.append(None)
                continue
            valid = np.array([v is not None for v in values], dtype=bool)
            if typ.is_string:
                vocab: List[str] = []
                lookup: Dict[str, int] = {}
                codes = np.full(len(values), -1, dtype=np.int32)
                for i, v in enumerate(values):
                    if v is None:
                        continue
                    if isinstance(typ, CharType):
                        v = str(v).ljust(typ.length)
                    code = lookup.get(v)
                    if code is None:
                        code = lookup[v] = len(vocab)
                        vocab.append(v)
                    codes[i] = code
                arrays.append(codes)
                dictionaries.append(tuple(vocab))
            else:
                storage = [typ.to_storage(v) if v is not None else typ.null_storage() for v in values]
                arrays.append(np.asarray(storage))
                dictionaries.append(None)
            validities.append(valid)
        schema = Schema(schema_fields)
        out = Batch.from_arrays(
            schema, arrays, validities, dictionaries, capacity=capacity, num_rows=n
        )
        if composite:
            cols = list(out.columns)
            for i, (typ, values) in composite.items():
                cols[i] = make_array_column(typ, values, out.capacity)
            out = Batch(schema, cols, out.row_mask)
        return out

    # -- export -------------------------------------------------------------
    def to_pylist(self) -> List[Tuple]:
        """Decode live rows to python tuples (for tests / client results):
        the answer's fetch, one ``device-sync`` (``what="result"``) over
        every column's transfer."""
        host = device_sync(
            "result", (self.row_mask,
                       [(c.data, c.validity) for c in self.columns]))
        mask = np.asarray(host[0])
        out_cols = []
        for col, (data, valid) in zip(self.columns, host[1]):
            if isinstance(col.type, (ArrayType, MapType)):
                out_cols.append(_composite_to_pylist(
                    Column(col.type, data, valid, col.dictionary), mask))
                continue
            data = np.asarray(data)[mask]
            valid = np.asarray(valid)[mask]
            vals: List[Any] = []
            for d, v in zip(data, valid):
                if not v:
                    vals.append(None)
                elif col.type.is_string:
                    code = int(d)
                    vals.append(col.dictionary[code] if col.dictionary and 0 <= code < len(col.dictionary) else None)
                else:
                    vals.append(col.type.from_storage(d))
            out_cols.append(vals)
        return [tuple(r) for r in zip(*out_cols)] if out_cols else []

    # -- transforms ---------------------------------------------------------
    def compact(self, capacity: Optional[int] = None, *,
                check: bool = True) -> "Batch":
        """Gather live rows to the front (device-side, static output shape).

        ``capacity`` smaller than the live-row count would silently drop rows;
        callers shrinking buckets must check ``host_count()`` first, so guard.
        Pass ``check=False`` from traced (jit/shard_map) contexts where the
        bound is guaranteed by construction — the guard needs a host sync.
        """
        cap = capacity or self.capacity
        if check and capacity is not None and capacity < self.capacity:
            live = self.host_count()
            if live > capacity:
                raise ValueError(
                    f"compact capacity {capacity} < live rows {live}"
                )
        idx, n = live_indices(self.row_mask, cap)
        new_mask = jnp.arange(cap, dtype=jnp.int32) < n
        cols = []
        for c in self.columns:
            cols.append(
                Column(
                    c.type,
                    jax.tree_util.tree_map(
                        lambda a: jnp.take(a, idx, axis=0), c.data),
                    jnp.take(c.validity, idx, axis=0) & new_mask,
                    c.dictionary,
                )
            )
        return Batch(self.schema, cols, new_mask)

    def pad(self, capacity: int) -> "Batch":
        """Grow to a larger capacity with dead padding lanes — the
        inverse of compact. The scan pipeline pads a split's ragged
        final chunk up to the stream's standard bucket so shape-keyed
        executables (ops/jitcache) are reused instead of recompiled per
        residual size. Padding lanes are dead (row_mask/validity False),
        so results are unchanged."""
        if capacity <= self.capacity:
            return self
        extra = capacity - self.capacity

        def grow(a):
            widths = [(0, extra)] + [(0, 0)] * (a.ndim - 1)
            return jnp.pad(a, widths)

        cols = [
            Column(c.type, jax.tree_util.tree_map(grow, c.data),
                   grow(c.validity), c.dictionary)
            for c in self.columns
        ]
        return Batch(self.schema, cols, grow(self.row_mask))

    def prefix(self, capacity: int) -> "Batch":
        """The first ``capacity`` lanes: a slice, for a batch whose live
        rows come first (what the sort-path group-by gives back); the
        caller knows that they fit."""
        if capacity >= self.capacity:
            return self

        def cut(a):
            return a[:capacity]

        cols = [
            Column(c.type, jax.tree_util.tree_map(cut, c.data),
                   cut(c.validity), c.dictionary)
            for c in self.columns
        ]
        return Batch(self.schema, cols, cut(self.row_mask))

    def __repr__(self) -> str:
        return f"Batch({self.schema!r}, capacity={self.capacity})"


jax.tree_util.register_pytree_node(
    Batch, Batch.tree_flatten, Batch.tree_unflatten
)


def _rows_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive cumsum of a 1-D int32 array in two levels: along rows
    of 1024 lanes, then over the rows' totals. The same numbers as
    ``jnp.cumsum``, whose 1-D form over 2^20 lanes takes XLA's TPU
    compiler ~19 s (this: ~1 s; ``tools/compact_probe.py``)."""
    n = x.shape[0]
    rows = n // 1024 if n % 1024 == 0 else 1
    within = jnp.cumsum(x.reshape(rows, -1), axis=1)
    total = within[:, -1]
    return (within + (jnp.cumsum(total) - total)[:, None]).reshape(-1)


def live_indices(mask: jax.Array, size: int) -> Tuple[jax.Array, jax.Array]:
    """The indices of ``mask``'s first ``size`` live lanes, ascending,
    padded with the last lane's index; and the live count (both int32).

    Element for element ``jnp.nonzero(mask, size=size,
    fill_value=len(mask) - 1)[0]``, which JAX 0.9.0 computes through a
    scatter-add (``bincount``) of one update a lane, in emulated int64
    under ``jax_enable_x64``: ~100 ms a 2^20-lane mask on the v5e, and
    TPC-H Q6 spent 6.1 of its 6.5 s there. This is a compress network
    with no scatter, gather or sort in it: a live lane has to move left
    by the number of dead lanes before it, and moves by that distance's
    bits, lowest first, one elementwise pass over the lanes a bit. Two
    live lanes never meet: after the passes for bits 0..k-1 their
    distance is 1 + 2^k (d_j // 2^k - d_i // 2^k) >= 1. Which of the
    candidates is fastest on the chip: ``tools/compact_probe.py``."""
    capacity = mask.shape[0]
    lane = jnp.arange(capacity, dtype=jnp.int32)
    dead_before = _rows_cumsum((~mask).astype(jnp.int32))
    n = capacity - dead_before[-1]
    # a lane's word: twice the distance its row has to move, plus one
    # where it holds a live row; a row carries the whole distance along,
    # so its origin is where it ends up plus that distance
    word = jnp.where(mask, 2 * dead_before + 1, 0)
    for k in range((capacity - 1).bit_length()):
        right = jnp.concatenate(
            [word[1 << k:], jnp.zeros(1 << k, jnp.int32)])
        word = jnp.where((right >> (k + 1)) & 1 == 1, right,
                         jnp.where((word >> (k + 1)) & 1 == 1, 0, word))
    idx = jnp.where(word & 1 == 1, lane + (word >> 1), capacity - 1)
    if size <= capacity:
        return idx[:size], n
    return jnp.pad(idx, (0, size - capacity),
                   constant_values=capacity - 1), n


def shift_lanes(x: jax.Array, by: int) -> jax.Array:
    """``x`` moved ``by`` lanes towards lane 0 along its first axis
    (``out[i] = x[i + by]``; towards the end for a negative ``by``),
    zeros moving in: a concatenation of slices, no gather."""
    if by == 0:
        return x
    fill = jnp.zeros_like(x[:abs(by)])
    return (jnp.concatenate([x[by:], fill]) if by > 0
            else jnp.concatenate([fill, x[:by]]))


def compress_moves(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The compress network of :func:`live_indices` as a plan that moves
    DATA: (moves, live count), ``moves`` one int32 a lane whose bit k
    says that pass k takes the lane 2^k to the right. Applied to a
    column by :func:`compress_lanes`, the live lanes end up at the
    front in their order, with no gather: log2(capacity) elementwise
    passes a column, where a gather by ``live_indices`` costs the v5e
    ~11 ns a lane a column (PERF.md section 5)."""
    capacity = mask.shape[0]
    dead_before = _rows_cumsum((~mask).astype(jnp.int32))
    word = jnp.where(mask, 2 * dead_before + 1, 0)
    moves = jnp.zeros(capacity, jnp.int32)
    for k in range((capacity - 1).bit_length()):
        right = shift_lanes(word, 1 << k)
        take = (right >> (k + 1)) & 1
        moves = moves | (take << k)
        word = jnp.where(take == 1, right,
                         jnp.where((word >> (k + 1)) & 1 == 1, 0, word))
    return moves, capacity - dead_before[-1]


def per_lane(flag: jax.Array, like: jax.Array) -> jax.Array:
    """A flag a lane shaped to broadcast against ``like``, whose lanes
    run along its first axis (a state column may hold a row a lane)."""
    return flag.reshape((-1,) + (1,) * (like.ndim - 1))


def compress_lanes(moves: jax.Array, x: jax.Array) -> jax.Array:
    """``x`` (lanes along its first axis) with the live lanes of
    :func:`compress_moves`' mask at the front, in their order; what the
    lanes behind them hold is not defined."""
    for k in range((moves.shape[0] - 1).bit_length()):
        x = jnp.where(per_lane((moves >> k) & 1 == 1, x),
                      shift_lanes(x, 1 << k), x)
    return x


def _composite_to_pylist(col: Column, mask: np.ndarray) -> List[Any]:
    """Decode an ARRAY/MAP column's live rows to python lists/dicts."""
    def decode_elem(typ, d, vocab):
        if typ.is_string:
            code = int(d)
            return (vocab[code] if vocab and 0 <= code < len(vocab)
                    else None)
        return typ.from_storage(d)

    valid = np.asarray(jax.device_get(col.validity))[mask]
    if isinstance(col.type, ArrayType):
        values, lengths, elem_valid = (np.asarray(a) for a in col.data)
        values, lengths, elem_valid = values[mask], lengths[mask], elem_valid[mask]
        et = col.type.element
        out: List[Any] = []
        for i, v in enumerate(valid):
            if not v:
                out.append(None)
                continue
            row = []
            for j in range(int(lengths[i])):
                row.append(decode_elem(et, values[i, j], col.dictionary)
                           if elem_valid[i, j] else None)
            out.append(row)
        return out
    # MAP
    keys, values, lengths, val_valid = (np.asarray(a) for a in col.data)
    keys, values = keys[mask], values[mask]
    lengths, val_valid = lengths[mask], val_valid[mask]
    kt, vt = col.type.key, col.type.value
    kd, vd = col.dictionary or (None, None)
    out = []
    for i, v in enumerate(valid):
        if not v:
            out.append(None)
            continue
        m = {}
        for j in range(int(lengths[i])):
            k = decode_elem(kt, keys[i, j], kd)
            m[k] = (decode_elem(vt, values[i, j], vd)
                    if val_valid[i, j] else None)
        out.append(m)
    return out


def make_array_column(typ: ArrayType, values: Sequence[Optional[Sequence]],
                      cap: int) -> Column:
    """Build an ARRAY column from python lists (None = NULL row)."""
    et = typ.element
    max_len = max([len(v) for v in values if v is not None] + [1])
    data = np.zeros((cap, max_len), dtype=np.dtype(et.storage_dtype))
    lengths = np.zeros(cap, dtype=np.int32)
    elem_valid = np.zeros((cap, max_len), dtype=bool)
    row_valid = np.zeros(cap, dtype=bool)
    vocab: List[str] = []
    lookup: Dict[str, int] = {}
    for i, row in enumerate(values):
        if row is None:
            continue
        row_valid[i] = True
        lengths[i] = len(row)
        for j, e in enumerate(row):
            if e is None:
                continue
            elem_valid[i, j] = True
            if et.is_string:
                code = lookup.get(e)
                if code is None:
                    code = lookup[e] = len(vocab)
                    vocab.append(e)
                data[i, j] = code
            else:
                data[i, j] = et.to_storage(e)
    return Column(typ, (jnp.asarray(data), jnp.asarray(lengths),
                        jnp.asarray(elem_valid)), jnp.asarray(row_valid),
                  tuple(vocab) if et.is_string else None)


def _concat_array_columns(cols: Sequence[Column], cap: int) -> Column:
    """Concatenate ARRAY columns along rows, padding widths to the max."""
    typ = cols[0].type
    max_len = max(c.data[0].shape[1] for c in cols)
    if typ.element.is_string:
        vocab, remaps = unify_dictionaries(cols)
        dictionary: Optional[Tuple[str, ...]] = vocab
    else:
        vocab, remaps, dictionary = None, None, None
    vals, lens, evs, rvs = [], [], [], []
    for ci, c in enumerate(cols):
        v, ln, ev = c.data
        pad = max_len - v.shape[1]
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)))
            ev = jnp.pad(ev, ((0, 0), (0, pad)))
        if remaps is not None:
            table = jnp.asarray(remaps[ci])
            idx = jnp.where(v >= 0, v, len(remaps[ci]) - 1)
            v = jnp.take(table, idx, axis=0)
        vals.append(v)
        lens.append(ln)
        evs.append(ev)
        rvs.append(c.validity)
    def cat_pad(parts, width=None):
        out = jnp.concatenate(parts)
        pad = cap - out.shape[0]
        if pad > 0:
            padding = ((0, pad),) + ((0, 0),) * (out.ndim - 1)
            out = jnp.pad(out, padding)
        return out
    return Column(typ, (cat_pad(vals), cat_pad(lens), cat_pad(evs)),
                  cat_pad(rvs), dictionary)


#: vocabularies merged entry by entry on the host
#: (`unify_dictionaries`), and the entries they held: a Python loop over
#: every string, so a text column with a value a row shows here
_UNIFIED = REGISTRY.counter("dictionary_unify_total")
_UNIFIED_ENTRIES = REGISTRY.counter("dictionary_unify_entries_total")


#: the last few large unifications, by the IDENTITY of the vocabularies
#: that went in (the entry holds them, so an id is not reused while it
#: is here): a scan-cached text column brings the same tuples query
#: after query, and TPC-H Q18's 1.5M customer names took the loop below
#: a second or two of every query
_UNIFY_MEMO: "Dict[Tuple[int, ...], tuple]" = {}
_UNIFY_MEMO_ENTRIES = 8
_UNIFY_MEMO_FLOOR = 1 << 12


def unify_dictionaries(columns: Sequence[Column]) -> Tuple[Tuple[str, ...], List[np.ndarray]]:
    """Merge per-column vocabularies; return (vocab, remap arrays per column).

    remap[i] maps old codes of columns[i] to codes in the unified vocab; -1
    stays -1 via the sentinel slot appended at the end.
    """
    sources = [col.dictionary or () for col in columns]
    entries = sum(len(src) for src in sources)
    key = tuple(id(src) for src in sources)
    memo = _UNIFY_MEMO.get(key) if entries >= _UNIFY_MEMO_FLOOR else None
    if memo is not None and all(a is b for a, b in zip(memo[0], sources)):
        return memo[1], memo[2]
    _UNIFIED.inc()
    _UNIFIED_ENTRIES.inc(entries)
    vocab: List[str] = []
    lookup: Dict[str, int] = {}
    remaps: List[np.ndarray] = []
    for src in sources:
        remap = np.full(len(src) + 1, -1, dtype=np.int32)  # last slot: -1 sentinel
        for old_code, s in enumerate(src):
            code = lookup.get(s)
            if code is None:
                code = lookup[s] = len(vocab)
                vocab.append(s)
            remap[old_code] = code
        remaps.append(remap)
    unified = tuple(vocab)
    if entries >= _UNIFY_MEMO_FLOOR:
        while len(_UNIFY_MEMO) >= _UNIFY_MEMO_ENTRIES:
            _UNIFY_MEMO.pop(next(iter(_UNIFY_MEMO)))
        _UNIFY_MEMO[key] = (sources, unified, remaps)
    return unified, remaps


def apply_remap_np(codes: np.ndarray, remap: np.ndarray) -> np.ndarray:
    """Host-side dictionary code remap (-1 maps through the sentinel)."""
    idx = np.where(codes >= 0, codes, len(remap) - 1)
    return remap[idx]


def vocab_column(vocab: Optional[Tuple[str, ...]]) -> Column:
    """Dummy 1-slot column carrying only a vocabulary — lets host code
    reuse unify_dictionaries without real data."""
    from .types import VARCHAR
    return Column(VARCHAR, jnp.zeros(1, dtype=jnp.int32),
                  jnp.zeros(1, dtype=bool), vocab)


def remap_codes(col: Column, remap: np.ndarray, vocab: Tuple[str, ...]) -> Column:
    """Apply a dictionary remap on device (gather)."""
    table = jnp.asarray(remap)
    # codes may be -1 (null padding): index the appended sentinel slot
    idx = jnp.where(col.data >= 0, col.data, len(remap) - 1)
    return Column(col.type, jnp.take(table, idx, axis=0), col.validity, vocab)


def concat_batches(batches: Sequence[Batch], capacity: Optional[int] = None) -> Batch:
    """Concatenate batches of identical schema (host orchestration op)."""
    assert batches, "concat of zero batches"
    schema = batches[0].schema
    total_cap = sum(b.capacity for b in batches)
    cap = capacity or bucket_capacity(total_cap)
    ncols = len(schema)
    out_cols = []
    for i in range(ncols):
        cols = [b.columns[i] for b in batches]
        typ = cols[0].type
        if isinstance(typ, ArrayType):
            out_cols.append(_concat_array_columns(cols, cap))
            continue
        if isinstance(typ, MapType):
            raise NotImplementedError("concat of MAP columns")
        if typ.is_string and all(c.dictionary is cols[0].dictionary
                                 for c in cols):
            dictionary = cols[0].dictionary     # one vocabulary: as it is
        elif typ.is_string:
            vocab, remaps = unify_dictionaries(cols)
            cols = [remap_codes(c, r, vocab) for c, r in zip(cols, remaps)]
            dictionary = vocab
        else:
            dictionary = None
        data = jnp.concatenate([c.data for c in cols])
        validity = jnp.concatenate([c.validity for c in cols])
        pad = cap - data.shape[0]
        if pad > 0:
            # pad only the row axis: vector-state columns (HLL registers)
            # carry a trailing width dimension
            data = jnp.pad(data,
                           ((0, pad),) + ((0, 0),) * (data.ndim - 1))
            validity = jnp.pad(validity, (0, pad))
        elif pad < 0:
            raise ValueError("concat capacity too small")
        out_cols.append(Column(typ, data, validity, dictionary))
    mask = jnp.concatenate([b.row_mask for b in batches])
    if cap - mask.shape[0] > 0:
        mask = jnp.pad(mask, (0, cap - mask.shape[0]))
    return Batch(schema, out_cols, mask)
