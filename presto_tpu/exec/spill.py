"""Two-tier spill: host DRAM first, then compressed pages on disk.

The TPU reshape of the reference's spill stack (reference
presto-main/.../spiller/GenericPartitioningSpiller.java for partitioned
join spill, operator/aggregation/builder/SpillableHashAggregationBuilder.java
for agg state, OrderByOperator.java + FileSingleStreamSpiller.java for
sort): the first "disk" is host DRAM (device_get), the natural spill tier
on a TPU host; when staged host bytes cross the pool's disk threshold,
chunks flush as compressed wire pages (exec/pages.py serde — the
reference's PagesSerde+LZ4 role) to a per-store temp file, partition-
sliced so readback is ranged reads. Partition ids are computed ON DEVICE
with the same value-based splitmix64 row hash the exchange uses — so a
spilled build partition and its probe partition agree by construction,
including for dictionary-encoded strings (hashed by VALUE, not per-chunk
code).

Buffers accumulate device batches against an OperatorMemoryContext; when
the pool can't fit the next batch (or another operator revokes them) they
stage everything to host numpy arrays and keep accepting input host-side.
Each staged chunk is bucketed once at staging time (argsort of partition
ids), so per-partition readback is slicing, not a rescan.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..batch import (
    Batch, Schema, apply_remap_np, bucket_capacity, concat_batches,
    unify_dictionaries, vocab_column,
)
from ..memory import QueryMemoryPool, batch_device_bytes
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER, device_sync
from ..ops.aggregation import AggSpec
from ..ops.jitcache import (
    compact_jit, finish_states_jit, grouped_aggregate_jit as grouped_aggregate,
    merge_pair_jit, merge_states_jit, pad_capacity_jit, prefix_jit)
from ..ops.sort import SortKey, sort_batch
from ..parallel.exchange import hash_partition_ids

#: process-wide spill metrics (per-query figures live on the pool's
#: MemoryStats; these are the fleet view behind system.runtime.metrics)
_SPILL_DEVICE_BYTES = REGISTRY.counter("spill_device_bytes_total")
_SPILL_HOST_BYTES = REGISTRY.counter("spill_host_staged_bytes_total")
_SPILL_DISK_BYTES = REGISTRY.counter("spill_disk_bytes_total")
_SPILL_REVOCATIONS = REGISTRY.counter("spill_revocations_total")


@dataclasses.dataclass
class _StagedChunk:
    datas: List[np.ndarray]
    valids: List[np.ndarray]
    dicts: List[Optional[Tuple[str, ...]]]
    part_rows: np.ndarray              # live row indices, partition-sorted
    bounds: Optional[np.ndarray]       # partition p = part_rows[b[p]:b[p+1]]

    def rows_of(self, p: Optional[int]) -> np.ndarray:
        if p is None or self.bounds is None:
            return self.part_rows
        return self.part_rows[self.bounds[p]:self.bounds[p + 1]]


def _stage_chunk(batch: Batch, pid=None,
                 n_partitions: Optional[int] = None) -> _StagedChunk:
    mask = np.asarray(batch.row_mask)
    live = np.nonzero(mask)[0]
    if pid is None:
        part_rows, bounds = live, None
    else:
        p = np.asarray(pid)[live]
        order = np.argsort(p, kind="stable")
        part_rows = live[order]
        bounds = np.searchsorted(p[order], np.arange(n_partitions + 1))
    return _StagedChunk(
        datas=[np.asarray(c.data) for c in batch.columns],
        valids=[np.asarray(c.validity) for c in batch.columns],
        dicts=[c.dictionary for c in batch.columns],
        part_rows=part_rows, bounds=bounds)


def _gather_chunks(schema: Schema,
                   selections: Iterable[Tuple[_StagedChunk, np.ndarray]]):
    """Concatenate selected rows across staged chunks, unifying string
    dictionaries incrementally. Returns (arrays, validity, vocabs) or
    None when no rows are selected."""
    ncols = len(schema)
    datas: List[List[np.ndarray]] = [[] for _ in range(ncols)]
    valids: List[List[np.ndarray]] = [[] for _ in range(ncols)]
    vocabs: List[Optional[Tuple[str, ...]]] = [None] * ncols
    any_rows = False
    for ch, rows in selections:
        if rows.size == 0:
            continue
        any_rows = True
        for ci in range(ncols):
            d = ch.datas[ci][rows]
            v = ch.valids[ci][rows]
            if ch.dicts[ci] is not None:
                if vocabs[ci] is None:
                    vocabs[ci] = ch.dicts[ci]
                elif vocabs[ci] != ch.dicts[ci]:
                    merged, remaps = unify_dictionaries(
                        [vocab_column(vocabs[ci]),
                         vocab_column(ch.dicts[ci])])
                    vocabs[ci] = merged
                    datas[ci] = [apply_remap_np(a, remaps[0])
                                 for a in datas[ci]]
                    d = apply_remap_np(d, remaps[1])
            datas[ci].append(d)
            valids[ci].append(v)
    if not any_rows:
        return None
    arrays = [np.concatenate(datas[ci]) for ci in range(ncols)]
    valid_arr = [np.concatenate(valids[ci]) for ci in range(ncols)]
    return arrays, valid_arr, vocabs


class SpillFile:
    """Append-only spill file of compressed wire pages (the role of
    reference spiller/FileSingleStreamSpiller.java's async file IO,
    synchronous here — staging already decoupled the device).

    Two construction modes share one read/append surface:

    - anonymous (default): a mkstemp'd scratch file unlinked on close —
      the spill tier's lifetime is the operator's;
    - named (``path=``, ``delete=False``): a durable file at a caller-
      chosen location that SURVIVES close — the exchange spool
      (exec/spool.py) builds its page logs on this, where another
      process (or a consumer that outlives the writer) reads the bytes
      back after the writing task is gone. ``flush()`` makes appended
      bytes visible to those foreign readers.
    """

    def __init__(self, directory: Optional[str] = None,
                 path: Optional[str] = None, delete: bool = True):
        self.delete = delete
        if path is not None:
            self.path = path
            self._f = open(path, "a+b")
        else:
            fd, self.path = tempfile.mkstemp(
                prefix="presto-tpu-spill-", suffix=".bin", dir=directory)
            self._f = os.fdopen(fd, "w+b")

    def append(self, data: bytes) -> Tuple[int, int]:
        off = self._f.seek(0, os.SEEK_END)
        self._f.write(data)
        return off, len(data)

    def flush(self) -> None:
        """Push appended bytes to the OS so concurrent readers (spool
        consumers in another process) observe complete frames."""
        self._f.flush()

    def read(self, off: int, length: int) -> bytes:
        self._f.seek(off)
        return self._f.read(length)

    def close(self) -> None:
        try:
            self._f.close()
        finally:
            if self.delete:
                try:
                    os.unlink(self.path)
                except OSError:
                    pass


def _chunk_host_bytes(ch: _StagedChunk) -> int:
    return sum(a.nbytes for a in ch.datas) + sum(v.nbytes for v in ch.valids)


class HostPartitionStore:
    """Rows staged to host DRAM, hash-partitioned by key columns; beyond
    ``disk_threshold`` staged bytes, chunks flush to a SpillFile as one
    compressed page per (chunk, partition)."""

    def __init__(self, schema: Schema, n_partitions: int,
                 pool: Optional[QueryMemoryPool] = None):
        self.schema = schema
        self.n = n_partitions
        self.chunks: List[_StagedChunk] = []
        self.pool = pool
        self.host_bytes = 0
        self._file: Optional[SpillFile] = None
        # per partition: [(offset, length)] fragments in the spill file
        self._frags: List[List[Tuple[int, int]]] = [
            [] for _ in range(n_partitions)]

    def add(self, batch: Batch, key_cols: Sequence[int]) -> int:
        """Stage a device batch; returns the device bytes it occupied."""
        if self.n == 1:
            ch = _stage_chunk(batch)        # single partition: no hashing
        elif not key_cols:
            # bounds=None would alias every row into all n partitions
            raise ValueError(
                "multi-partition staging requires key columns")
        else:
            pid = hash_partition_ids(batch, list(key_cols), self.n)
            ch = _stage_chunk(batch, pid, self.n)
        if self._file is not None:
            self._flush_chunk(ch)
        else:
            self.chunks.append(ch)
            nb = _chunk_host_bytes(ch)
            self.host_bytes += nb
            _SPILL_HOST_BYTES.inc(nb)
            pool = self.pool
            if pool is not None:
                # the staging budget is QUERY-wide (reference
                # NodeSpillConfig.maxSpillPerNode): all stores share the
                # pool counter, so N concurrent buffers can't each claim
                # the full threshold
                pool.host_staged_bytes += nb
                if (pool.disk_threshold is not None
                        and pool.host_staged_bytes > pool.disk_threshold):
                    self._flush_to_disk()
        nb_dev = batch_device_bytes(batch)
        _SPILL_DEVICE_BYTES.inc(nb_dev)
        return nb_dev

    def _flush_to_disk(self) -> None:
        with TRACER.span("spill-to-disk", chunks=len(self.chunks),
                         host_bytes=self.host_bytes):
            self._file = SpillFile(
                None if self.pool is None else self.pool.spill_dir)
            for ch in self.chunks:
                self._flush_chunk(ch)
            self.chunks = []
        if self.pool is not None:
            self.pool.host_staged_bytes -= self.host_bytes
        self.host_bytes = 0

    def _flush_chunk(self, ch: _StagedChunk) -> None:
        from .pages import _encode
        for p in range(self.n):
            rows = ch.rows_of(p)
            if rows.size == 0:
                continue
            page = _encode(self.schema,
                           [d[rows] for d in ch.datas],
                           [v[rows] for v in ch.valids],
                           ch.dicts, compress=True)
            self._frags[p].append(self._file.append(page))
            _SPILL_DISK_BYTES.inc(len(page))
            if self.pool is not None:
                self.pool.stats.disk_spilled_bytes += len(page)

    def _disk_chunks(self, p: int) -> Iterator[Tuple[_StagedChunk, np.ndarray]]:
        from .pages import deserialize_arrays
        for off, length in self._frags[p]:
            _, arrays, valids, dicts, n = deserialize_arrays(
                self._file.read(off, length))
            ch = _StagedChunk(datas=arrays, valids=valids, dicts=dicts,
                              part_rows=np.arange(n), bounds=None)
            yield ch, ch.part_rows

    def _partition_arrays(self, p: int):
        selections = [(ch, ch.rows_of(p)) for ch in self.chunks]
        if self._file is not None:
            selections.extend(self._disk_chunks(p))
        return _gather_chunks(self.schema, selections)

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None
        if self.pool is not None and self.host_bytes:
            self.pool.host_staged_bytes -= self.host_bytes
            self.host_bytes = 0

    def partition_batch(self, p: int) -> Optional[Batch]:
        """The whole partition as one device batch (build sides)."""
        got = self._partition_arrays(p)
        if got is None:
            return None
        arrays, valids, vocabs = got
        n = len(arrays[0]) if arrays else 0
        if n == 0:
            return None
        return Batch.from_arrays(self.schema, arrays, valids, vocabs,
                                 num_rows=n)

    def partition_batches(self, p: int,
                          rows_per_batch: int) -> Iterator[Batch]:
        """The partition streamed in bounded device chunks (probe sides)."""
        got = self._partition_arrays(p)
        if got is None:
            return
        arrays, valids, vocabs = got
        n = len(arrays[0]) if arrays else 0
        for lo in range(0, n, rows_per_batch):
            hi = min(lo + rows_per_batch, n)
            yield Batch.from_arrays(
                self.schema, [a[lo:hi] for a in arrays],
                [v[lo:hi] for v in valids], vocabs, num_rows=hi - lo)


class SpillableBuildBuffer:
    """Join-build-side accumulator: device-resident until the pool forces
    host staging (reference HashBuilderOperator spill states :155-180).
    finish() returns None (empty), a device Batch, or a
    HostPartitionStore for partitioned probing."""

    def __init__(self, pool: QueryMemoryPool, name: str,
                 key_cols: Sequence[int], n_partitions: int):
        self.ctx = pool.context(name, revoke_cb=self._spill_all)
        self.key_cols = list(key_cols)
        self.n_partitions = n_partitions
        self.device: List[Batch] = []
        self.store: Optional[HostPartitionStore] = None
        self.spilled = False

    def add(self, b: Batch) -> None:
        # pool lock: the pool's revoke path calls _spill_all from OTHER
        # threads (build drain on the main thread vs probe-prefetch); an
        # unsynchronized revoke both stages and leaves batches visible to
        # a concurrent consumer — duplicated rows
        with self.ctx.pool.lock:
            if self.spilled:
                self._stage(b)
                return
            nb = batch_device_bytes(b)
            if self.ctx.pool.try_reserve(nb, self.ctx):
                self.device.append(b)
            else:
                self.ctx.revoke()  # spills everything accumulated so far
                self._stage(b)

    def _stage(self, b: Batch) -> int:
        if self.store is None:
            self.store = HostPartitionStore(b.schema, self.n_partitions,
                                            pool=self.ctx.pool)
        n = self.store.add(b, self.key_cols)
        self.ctx.pool.stats.spilled_bytes += n
        return n

    def _spill_all(self) -> int:
        _SPILL_REVOCATIONS.inc()
        with TRACER.span("spill-revoke", buffer="join-build",
                         batches=len(self.device)):
            freed = 0
            for b in self.device:
                freed += self._stage(b)
            self.device = []
            self.spilled = True
            return freed

    def finish(self):
        # once the build is handed to the prober, revoking can no longer
        # free its device memory — keep the reservation, end revocability
        with self.ctx.pool.lock:
            self.ctx.pin()
            if self.spilled:
                return self.store
            if not self.device:
                return None
            return (self.device[0] if len(self.device) == 1
                    else concat_batches(self.device))

    def close(self) -> None:
        self.ctx.close()
        if self.store is not None:
            self.store.close()


#: the grouped state (`AggSpillBuffer`): partial states taken in, merges
#: of two states into one (the final fold's among them), the lanes those
#: merges read, and the live groups of each finished state
_AGG_PARTIALS = REGISTRY.counter("agg_partials_total")
_AGG_MERGES = REGISTRY.counter("agg_state_merges_total")
_AGG_LANES_MERGED = REGISTRY.counter("agg_state_lanes_merged_total")
_AGG_GROUPS = REGISTRY.counter("agg_state_groups_total")
#: network merges by what the program did, one count a merged state
#: whose live count is read (``_cut``): ``append``, one state ended
#: before the other began; ``network``, the merge network ran
_AGG_MERGE_SELECTED = {
    how: REGISTRY.counter(f"agg_merge_selected_total.{how}")
    for how in ("append", "network")}


@dataclasses.dataclass
class _State:
    """One state batch of the grouped aggregation on the device."""
    batch: Batch
    #: every key once (a partial's or a merge's output; not what an
    #: exchange hands a FINAL step, which may hold several producers')
    unique: bool
    #: live rows first, ascending by the sort path's operands: what
    #: ``merge_states`` takes
    normalized: bool
    #: the live groups, where they have been read back
    groups: Optional[int] = None
    #: ``merge_states``' flag (a device int32) until ``_cut`` reads it
    #: with the live count
    appended: Optional[jax.Array] = None


class AggSpillBuffer:
    """Grouped-aggregation state accumulator: merges partial-state batches
    on device; under memory pressure stages states to host partitioned by
    group-key hash, finalizing partition-serially (reference
    SpillableHashAggregationBuilder.java + MergingHashAggregationBuilder).
    Group keys are disjoint across hash partitions, so per-partition FINAL
    results concatenate to the global answer.

    On the device the state is a binary counter of states, at most one a
    capacity (a power of two): a partial is cut to the bucket of its live
    groups and takes its capacity's place; where one stands there already
    the two merge into one of twice the capacity, and so on up. Equal
    capacities only, so a query compiles ONE merge program a capacity
    whatever the number of its batches, and no state is sorted again
    whole for every sixteen partials that arrive. Two normalized states
    of integer keys merge in ``ops.aggregation.merge_states`` (no sort,
    no gather; where one ends before the other begins, as the states
    over an input clustered by the keys do, the program appends it and
    runs no network: ``agg_merge_selected_total``); any other pair in
    ``grouped_aggregate`` over their concatenation, inside the program.
    A live count is read a few states late (``LATE``), when the device
    has long computed it."""

    #: states whose live count is not read yet: the oldest is read when
    #: one more arrives, so the host stays this far ahead of the device
    LATE = 2
    #: a state this small is not worth a readback to cut it smaller
    CUT_FLOOR = 1 << 12

    def __init__(self, pool: QueryMemoryPool, name: str,
                 key_idx: Sequence[int], aggs: Sequence[AggSpec],
                 n_partitions: int,
                 key_bounds=None, allow_dense: bool = True,
                 error_sink=None):
        self.ctx = pool.context(name, revoke_cb=self._spill_all)
        self.key_idx = list(key_idx)
        self.aggs = list(aggs)
        # stats-derived static key bounds (AggregationNode.key_bounds):
        # merges and finals over state rows keep the dense scatter path;
        # allow_dense=False (session dense_grouping=false) pins the sort
        # path end to end
        self.key_bounds = tuple(key_bounds) if key_bounds else None
        self.allow_dense = allow_dense
        # receives device error scalars (executor error_flags.append):
        # a merge/final whose LARGER concatenated capacity flips the
        # dense gate on must still flag out-of-bounds keys, even when
        # the per-batch partials sorted (and so appended no flag)
        self.error_sink = error_sink
        self.n_partitions = n_partitions
        #: states not cut to their live count yet, oldest first
        self.late: List[_State] = []
        #: capacity -> the one state of that capacity
        self.levels: dict = {}
        self.store: Optional[HostPartitionStore] = None
        self.spilled = False

    # -- taking partials in --------------------------------------------------
    def add_partial(self, partial: Batch, unique: bool = True,
                    normalized: bool = False) -> None:
        """One more partial state. ``unique``: every key once (false for
        what an exchange hands a FINAL step); ``normalized``: the sort
        path made it (live rows first, in the sort's order)."""
        _AGG_PARTIALS.inc()
        # pool lock: revoke callbacks (_spill_all) arrive from other
        # threads mid-merge; see SpillableBuildBuffer.add
        with self.ctx.pool.lock:
            if self.spilled:
                self._stage(partial)
                return
            if not self.ctx.pool.try_reserve(batch_device_bytes(partial),
                                             self.ctx):
                self.ctx.revoke()
                self._stage(partial)
                return
            self.late.append(_State(partial, unique, unique and normalized))
        self._settle(self.LATE)

    def _settle(self, keep: int) -> None:
        """Place the oldest late states until ``keep`` are left: each is
        cut to its live count and takes its capacity's place, or merges
        with the state that stands there, the merged state late in its
        turn. The cuts and merges (readbacks, launches) run outside the
        lock so other operators' reserves aren't blocked behind device
        compute; a revoke landing meanwhile flips ``spilled``, and what
        is in flight here is staged."""
        while True:
            with self.ctx.pool.lock:
                if self.spilled or len(self.late) <= keep:
                    return
                st = self.late.pop(0)
            st = self._cut(st)
            with self.ctx.pool.lock:
                other = (None if self.spilled
                         else self.levels.pop(st.batch.capacity, None))
                if other is None:
                    self._hold(st, late=False)
                    continue
            merged = self._merge(other, st)
            with self.ctx.pool.lock:
                self._hold(merged, late=True)

    def _hold(self, st: _State, late: bool) -> None:
        """Under the lock: ``st`` among the late states or at its
        capacity's place, or to the host where a revoke has landed; the
        pool held to what the buffer holds now."""
        if self.spilled:
            self._stage(st.batch)
            return
        if late:
            self.late.append(st)
        else:
            self.levels[st.batch.capacity] = st
        self.ctx.release_all()
        held = self.late + list(self.levels.values())
        if not self.ctx.pool.try_reserve(
                sum(batch_device_bytes(s.batch) for s in held), self.ctx):
            self.ctx.revoke()

    def _cut(self, st: _State) -> _State:
        """``st`` with every key once (a state that may hold a key
        twice goes through the group-by, alone) and, behind a readback,
        at the bucket of its live groups."""
        b = st.batch
        if not st.unique:
            dense = self._dense(b, b.capacity)
            self._flag_bounds(b, dense)
            b = grouped_aggregate(b, self.key_idx, self.aggs, mode="merge",
                                  key_bounds=self.key_bounds,
                                  allow_dense=self.allow_dense)
            st = _State(b, True, not dense)
        groups = None
        if b.capacity > self.CUT_FLOOR:
            if st.appended is None:
                groups = b.host_count("agg-state-groups")
            else:
                groups, appended = (int(v) for v in device_sync(
                    "agg-state-groups", (b.count(), st.appended)))
                _AGG_MERGE_SELECTED[
                    "append" if appended else "network"].inc()
            cap = bucket_capacity(max(groups, 1), minimum=self.CUT_FLOOR)
            if cap < b.capacity:
                b = (prefix_jit(b, cap) if st.normalized
                     else compact_jit(b, cap))
        return _State(b, True, st.normalized, groups)

    def _dense(self, like: Batch, lanes: int) -> bool:
        """Host-only mirror of ``grouped_aggregate``'s dispatch for a
        merge over ``lanes`` lanes laid out as ``like``."""
        from ..ops.aggregation import (
            _wide_state_aggs, dense_group_plan, has_drain_agg)
        if not self.allow_dense or has_drain_agg(self.aggs) \
                or _wide_state_aggs(self.aggs):
            return False
        return dense_group_plan(like, self.key_idx, lanes,
                                self.key_bounds) is not None

    def _merge(self, a: _State, b: _State) -> _State:
        """Two states of unique keys as one; of one capacity where the
        counter merges them, and ``a`` padded to ``b``'s in the fold."""
        from ..ops.aggregation import merge_network_ok
        n_keys = len(self.key_idx)
        lanes = a.batch.capacity + b.batch.capacity
        same_dicts = all(
            x.dictionary is y.dictionary or x.dictionary == y.dictionary
            for x, y in zip(a.batch.columns, b.batch.columns))
        network = (a.normalized and b.normalized and same_dicts
                   and a.batch.capacity == b.batch.capacity
                   and lanes & (lanes - 1) == 0
                   and merge_network_ok(a.batch, n_keys, self.aggs))
        _AGG_MERGES.inc()
        _AGG_LANES_MERGED.inc(lanes)
        # groups_out: not known until the merged state's count is read,
        # LATE states from now (the fold and the finish read theirs)
        with TRACER.span("agg-merge", lanes_in=lanes, groups_out=-1,
                         mode="network" if network else "sort"):
            if network:
                merged, appended = merge_states_jit(a.batch, b.batch, n_keys,
                                                    self.aggs)
                return _State(merged, True, True, appended=appended)
            dense = self._dense(a.batch, lanes)
            self._flag_bounds(a.batch, dense)
            self._flag_bounds(b.batch, dense)
            if same_dicts:
                merged = merge_pair_jit(a.batch, b.batch, self.key_idx,
                                        self.aggs, self.key_bounds,
                                        self.allow_dense)
            else:
                # string columns under different dictionaries: their
                # codes are unified on the host, eagerly
                merged = grouped_aggregate(
                    concat_batches([a.batch, b.batch]), self.key_idx,
                    self.aggs, mode="merge", key_bounds=self.key_bounds,
                    allow_dense=self.allow_dense)
            return _State(merged, True, not dense)

    def _flag_bounds(self, states: Batch, dense: bool) -> None:
        """Where the dense (clamping) path engages for a merge over
        ``states``, emit the bounds-violation scalar — state batches
        keep raw key values, so out-of-bounds keys from a sort-path
        partial are still visible here (exec/local.py owns the
        per-partial-batch flags)."""
        if dense and self.key_bounds is not None \
                and self.error_sink is not None:
            from ..ops.jitcache import key_bounds_violation_jit
            self.error_sink(key_bounds_violation_jit(
                states, self.key_idx, self.key_bounds))

    def _stage(self, b: Batch) -> int:
        if self.store is None:
            self.store = HostPartitionStore(b.schema, self.n_partitions,
                                            pool=self.ctx.pool)
        n = self.store.add(b, self.key_idx)
        self.ctx.pool.stats.spilled_bytes += n
        return n

    def _spill_all(self) -> int:
        _SPILL_REVOCATIONS.inc()
        held = self.late + list(self.levels.values())
        with TRACER.span("spill-revoke", buffer="hash-agg",
                         batches=len(held)):
            freed = 0
            for st in held:
                freed += self._stage(st.batch)
            self.late, self.levels = [], {}
            self.spilled = True
            return freed

    # -- the finished state ---------------------------------------------------
    def _fold(self) -> Optional[_State]:
        """Everything held as ONE state of unique keys: the late states
        placed, then the levels folded from the smallest up, the folded
        state padded to the next level's capacity (the next is at least
        as large: it is the smallest left and larger than the last)."""
        self._settle(0)
        acc: Optional[_State] = None
        while True:
            with self.ctx.pool.lock:
                if self.spilled or not self.levels:
                    break
                st = self.levels.pop(min(self.levels))
            if acc is None:
                acc = st
                continue
            if acc.batch.capacity < st.batch.capacity:
                acc = _State(pad_capacity_jit(acc.batch, st.batch.capacity),
                             True, acc.normalized)
            acc = self._cut(self._merge(acc, st))
        if acc is not None and self.spilled:
            with self.ctx.pool.lock:
                self._stage(acc.batch)
            return None
        return acc

    def results(self, final: bool = True) -> Iterator[Batch]:
        """Final rows (default) or merged partial states (``final=False``,
        the PARTIAL-step output shipped to a downstream exchange)."""
        state = self._fold()
        with self.ctx.pool.lock:
            # consumers hold the yielded state from here on: a late
            # revoke can't re-stage what we are about to yield
            self.ctx.pin()
            spilled = self.spilled
        if not spilled:
            if state is None:
                return
            with TRACER.span("agg-merge", lanes_in=state.batch.capacity,
                             mode="finish") as span:
                # the live groups where a cut has read them: a state
                # that stayed under CUT_FLOOR is not read back for the
                # counter's sake (the host would wait for the device
                # where TPC-H Q1's sort overlaps it)
                if state.groups is not None:
                    _AGG_GROUPS.inc(state.groups)
                span.annotate(groups_out=-1 if state.groups is None
                              else state.groups)
                out = (finish_states_jit(state.batch, len(self.key_idx),
                                         self.aggs)
                       if final else state.batch)
            yield out
            return
        mode = "final" if final else "merge"
        for p in range(self.n_partitions):
            part = None if self.store is None else \
                self.store.partition_batch(p)
            if part is None:
                continue
            self._flag_bounds(part, self._dense(part, part.capacity))
            yield grouped_aggregate(part, self.key_idx, self.aggs,
                                    mode=mode, key_bounds=self.key_bounds,
                                    allow_dense=self.allow_dense)

    def close(self) -> None:
        self.ctx.close()
        if self.store is not None:
            self.store.close()


class SortSpillBuffer:
    """ORDER BY accumulator: device sort when everything fits; otherwise
    raw chunks stage to host and the final ordering is one np.lexsort over
    sortable operands replicating ops.sort._sortable's transforms
    (reference OrderByOperator spill; the host takes the role of
    FileSingleStreamSpiller's disk)."""

    def __init__(self, pool: QueryMemoryPool, name: str,
                 keys: Sequence[SortKey]):
        self.ctx = pool.context(name, revoke_cb=self._spill_all)
        self.keys = list(keys)
        self.device: List[Batch] = []
        self.store: Optional[HostPartitionStore] = None
        self.schema: Optional[Schema] = None
        self.spilled = False

    def add(self, b: Batch) -> None:
        # pool lock: cross-thread revoke callbacks; see
        # SpillableBuildBuffer.add
        with self.ctx.pool.lock:
            self.schema = b.schema
            if self.spilled:
                self._stage(b)
                return
            nb = batch_device_bytes(b)
            if self.ctx.pool.try_reserve(nb, self.ctx):
                self.device.append(b)
            else:
                self.ctx.revoke()
                self._stage(b)

    def _stage(self, b: Batch) -> int:
        if self.store is None:
            # one partition: sort wants everything back in one readback,
            # but still rides the two-tier (DRAM -> disk) staging
            self.store = HostPartitionStore(b.schema, 1,
                                            pool=self.ctx.pool)
        n = self.store.add(b, [])
        self.ctx.pool.stats.spilled_bytes += n
        return n

    def _spill_all(self) -> int:
        _SPILL_REVOCATIONS.inc()
        with TRACER.span("spill-revoke", buffer="order-by",
                         batches=len(self.device)):
            freed = 0
            for b in self.device:
                freed += self._stage(b)
            self.device = []
            self.spilled = True
            return freed

    def results(self, rows_per_batch: int) -> Iterator[Batch]:
        with self.ctx.pool.lock:
            self.ctx.pin()
            spilled, device = self.spilled, list(self.device)
        if not spilled:
            if not device:
                return
            merged = (device[0] if len(device) == 1
                      else concat_batches(device))
            yield sort_batch(merged, self.keys)
            return
        yield from self._host_sorted(rows_per_batch)

    def _host_sorted(self, rows_per_batch: int) -> Iterator[Batch]:
        schema = self.schema
        got = None if self.store is None \
            else self.store._partition_arrays(0)
        if got is None:
            return
        arrays, valid_arr, vocabs = got
        operands: List[np.ndarray] = []
        for k in self.keys:
            operands.extend(_np_sortable(
                arrays[k.column], valid_arr[k.column], vocabs[k.column],
                schema.types[k.column], k))
        # lexsort: last key is primary -> reverse; stable like lax.sort
        perm = np.lexsort(tuple(reversed(operands)))
        n = len(perm)
        for lo in range(0, n, rows_per_batch):
            idx = perm[lo:min(lo + rows_per_batch, n)]
            yield Batch.from_arrays(
                schema, [a[idx] for a in arrays],
                [v[idx] for v in valid_arr], vocabs, num_rows=len(idx))

    def close(self) -> None:
        self.ctx.close()
        if self.store is not None:
            self.store.close()


def _np_sortable(data: np.ndarray, valid: np.ndarray,
                 vocab: Optional[Tuple[str, ...]], typ,
                 key: SortKey) -> List[np.ndarray]:
    """Host replica of ops.sort._sortable: [null_rank, data'] ascending."""
    if typ.is_string:
        v = np.asarray(vocab or ("",), dtype=object)
        rank = np.argsort(np.argsort(v))
        data = rank[np.where(data >= 0, data, 0)]
    if data.dtype == np.bool_:
        data = data.astype(np.int32)
    if not key.ascending:
        data = -data if np.issubdtype(data.dtype, np.floating) else ~data
    nulls_first = key.effective_nulls_first()
    null_rank = (np.where(valid, 1, 0) if nulls_first
                 else np.where(valid, 0, 1)).astype(np.int32)
    return [null_rank, data]
