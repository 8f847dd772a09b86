"""Per-operator execution statistics.

The role of the reference's stats objects — OperatorStats/DriverStats
recorded by OperationTimer inside the Driver loop (reference
operator/Driver.java:380-385, operator/OperatorStats.java) and surfaced
through EXPLAIN ANALYZE (operator/ExplainAnalyzeOperator.java): every
plan-node iterator is wrapped to record wall time, batches, and (in
analyze mode, where a device sync per batch is acceptable) live rows.

Wall time is inclusive — a node's clock runs while it waits on its
children — so the printer reports exclusive time by subtracting child
inclusive times, mirroring how the reference separates operator wall
from blocked time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional


@dataclasses.dataclass
class NodeStats:
    wall_s: float = 0.0          # inclusive iterator time
    batches: int = 0
    rows: int = 0                # live rows (analyze mode only)
    capacity: int = 0            # total batch capacity emitted
    #: device seconds attributed by the profiler (profile mode only:
    #: jit dispatches made in this operator's frame, bracketed with
    #: block_until_ready — obs/profiler.py)
    device_time_s: float = 0.0


class StatsCollector:
    """Collects NodeStats keyed by plan node (structural equality, the
    same keying as the executor's shared-subplan cache, so a replayed
    duplicate subtree reports the stats of its one real execution)."""

    def __init__(self, count_rows: bool = False):
        self.count_rows = count_rows
        self.by_node: Dict[object, NodeStats] = {}
        self.total_wall_s: float = 0.0
        self.planning_s: float = 0.0
        #: per-split completion records from table scans (the reference's
        #: event/SplitMonitor.java split-completion events): dicts with
        #: table, split, wall_ms, batches, started_at
        self.splits: List[Dict] = []
        #: device scan-cache outcome per split (exec/scancache.py) and
        #: cumulative consumer-side prefetch stall — the EXPLAIN ANALYZE
        #: scan-cache line's feed
        self.cache_hits = 0
        self.cache_misses = 0
        self.prefetch_stall_s = 0.0
        #: plan node -> {ExecutableRecord: [invocations, device_s]} —
        #: which executables each operator dispatched while profiled;
        #: FLOPs/HBM bytes derive at render time (record.analyze() is
        #: lazy XLA introspection, never paid per call)
        self.exe_by_node: Dict[object, Dict[object, list]] = {}
        #: plan node -> (strategy, distribution) the join dispatch
        #: actually executed (direct/sorted/expand x replicated/
        #: partitioned) — the EXPLAIN ANALYZE join-row annotation and
        #: the per-query view of join_strategy_selected_total
        self.join_strategy: Dict[object, tuple] = {}
        import threading
        # record_cache fires from concurrent prefetch worker threads;
        # an unsynchronized += would drop increments
        self._cache_lock = threading.Lock()

    def record_cache(self, hit: bool) -> None:
        with self._cache_lock:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    def record_device(self, node, seconds: float, record) -> None:
        """Charge one profiled jit dispatch to a plan operator
        (obs/profiler.profiled_call's attribution sink)."""
        with self._cache_lock:
            st = self.by_node.setdefault(node, NodeStats())
            st.device_time_s += seconds
            ent = self.exe_by_node.setdefault(node, {}).setdefault(
                record, [0, 0.0])
            ent[0] += 1
            ent[1] += seconds

    def device_for(self, node) -> Optional[Dict]:
        """Per-operator device truth for the printer/history:
        ``device_time_s`` plus FLOPs / bytes-accessed estimates
        (per-invocation cost analysis x invocation count). None when
        the operator dispatched nothing under a profile context."""
        st = self.by_node.get(node)
        counts = self.exe_by_node.get(node)
        if (st is None or st.device_time_s <= 0.0) and not counts:
            return None
        flops = 0.0
        hbm = 0.0
        for rec, (n, _secs) in list((counts or {}).items()):
            a = rec.analyze()
            flops += (a.get("flops") or 0.0) * n
            hbm += (a.get("bytes_accessed") or 0.0) * n
        return {"device_time_s": st.device_time_s if st else 0.0,
                "flops": flops, "hbm_bytes": hbm}

    def executables_used(self) -> List[Dict]:
        """This query's executables, aggregated across operators —
        the EXPLAIN ANALYZE "Executables" section feed (the
        ``system.runtime.executables`` table is the process-lifetime
        view of the same records)."""
        agg: Dict[object, list] = {}
        for per_node in list(self.exe_by_node.values()):
            for rec, (n, secs) in list(per_node.items()):
                ent = agg.setdefault(rec, [0, 0.0])
                ent[0] += n
                ent[1] += secs
        out = []
        for rec, (n, secs) in agg.items():
            a = rec.analyze()
            out.append({
                "name": rec.name, "static_key": rec.static_key,
                "invocations": n, "device_time_s": secs,
                "compile_seconds": rec.compile_seconds,
                "flops": a.get("flops"),
                "bytes_accessed": a.get("bytes_accessed"),
            })
        out.sort(key=lambda d: -d["device_time_s"])
        return out

    def record_join_strategy(self, node, strategy: str,
                             distribution: str,
                             residual: Optional[str] = None) -> None:
        """Executed join-dispatch verdict for one join/semi-join
        operator (exec/local._Executor._note_join_strategy's sink);
        ``residual`` the form that decided a semi join's residual."""
        self.join_strategy[node] = (strategy, distribution, residual)

    def join_strategy_for(self, node) -> Optional[tuple]:
        return self.join_strategy.get(node)

    def record_split(self, table: str, split_no: int, started_at: float,
                     wall_s: float, batches: int) -> None:
        self.splits.append({
            "table": table, "split": split_no,
            "startMs": round(started_at * 1e3, 1),
            "wallMs": round(wall_s * 1e3, 1), "batches": batches})

    def snapshot(self) -> List[Dict]:
        """JSON-able per-node stats, root-last plan order — the live
        per-stage surface behind GET /v1/query/{id} (reference
        server/QueryResource.java per-stage stats)."""
        out = []
        # copy: the executor thread grows by_node while the live REST
        # endpoint snapshots it
        for node, st in list(self.by_node.items()):
            out.append({
                "node": type(node).__name__.replace("Node", ""),
                "wallMs": round(st.wall_s * 1e3, 1),
                "batches": st.batches,
                "rows": st.rows if self.count_rows else None,
                "capacity": st.capacity,
            })
        return out

    def stats_for(self, node) -> Optional[NodeStats]:
        return self.by_node.get(node)

    def wrap(self, node, it: Iterator) -> Iterator:
        st = self.by_node.setdefault(node, NodeStats())
        from ..obs.profiler import operator_scope

        def timed():
            while True:
                t0 = time.perf_counter()
                try:
                    # operator attribution: jit dispatches made while
                    # THIS node's generator frame runs charge to it;
                    # nested child iterators re-set the scope around
                    # their own frames (innermost wins), so a join's
                    # kernels bill the join, its child scan's staging
                    # bills the scan
                    with operator_scope(self, node):
                        b = next(it)
                except StopIteration:
                    st.wall_s += time.perf_counter() - t0
                    return
                st.wall_s += time.perf_counter() - t0
                st.batches += 1
                st.capacity += b.capacity
                if self.count_rows:
                    st.rows += b.host_count()
                yield b
        return timed()
