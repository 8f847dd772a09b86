"""Device-resident scan cache + prefetching scan pipeline
(exec/scancache.py): warm-hit parity, write invalidation, eviction
under a small memory limit, prefetcher shutdown hygiene, ragged-split
capacity padding, and the observability surfaces.
"""
import threading
import time

import pytest

from presto_tpu import types as T
from presto_tpu.batch import Batch, Schema
from presto_tpu.connectors.spi import (
    CatalogManager, Connector, ConnectorMetadata, ConnectorSplitManager,
    PageSource, Split, TableHandle,
)
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import scancache
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.exec.scancache import CACHE, ScanCache, ScanOptions
from presto_tpu.obs.metrics import REGISTRY

SF = 0.01


def _counter(name: str) -> float:
    return REGISTRY.counter(name).value


@pytest.fixture(autouse=True)
def fresh_cache():
    """Deterministic cache state per test; the process-wide limit is
    restored afterwards so other modules see the default."""
    CACHE.clear()
    yield
    CACHE.clear()
    CACHE.set_limit(scancache.DEFAULT_CACHE_BYTES)


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=SF)


# -- correctness: warm hits, escape hatch, invalidation ----------------------

def test_warm_hit_parity(runner):
    q = ("select l_returnflag, count(*), sum(l_extendedprice) "
         "from lineitem group by l_returnflag order by 1")
    cold = runner.execute(q).rows
    h0 = _counter("scan_cache_hit_total")
    warm = runner.execute(q).rows
    assert warm == cold
    assert _counter("scan_cache_hit_total") > h0
    # scan_cache=false escape hatch: same results, no cache traffic
    h1 = _counter("scan_cache_hit_total")
    m1 = _counter("scan_cache_miss_total")
    off = runner.execute(q, properties={"scan_cache": False}).rows
    assert off == cold
    assert _counter("scan_cache_hit_total") == h1
    assert _counter("scan_cache_miss_total") == m1


class _CountingConnector:
    """Delegate that counts page_source calls (decode work)."""

    def __init__(self, inner):
        self._inner = inner
        self.name = inner.name
        self.page_sources = 0

    @property
    def metadata(self):
        return self._inner.metadata

    @property
    def split_manager(self):
        return self._inner.split_manager

    def data_version(self, table):
        return self._inner.data_version(table)

    def page_source(self, split, columns, pushdown=None,
                    rows_per_batch=1 << 17):
        self.page_sources += 1
        return self._inner.page_source(split, columns, pushdown=pushdown,
                                       rows_per_batch=rows_per_batch)


def test_warm_run_skips_decode():
    counting = _CountingConnector(TpchConnector(sf=SF))
    catalogs = CatalogManager()
    catalogs.register("tpch", counting)
    r = LocalRunner(catalogs=catalogs)
    q = "select count(*), sum(o_totalprice) from orders"
    cold = r.execute(q).rows
    n_cold = counting.page_sources
    assert n_cold > 0
    warm = r.execute(q).rows
    assert warm == cold
    assert counting.page_sources == n_cold  # zero new decodes


def test_invalidation_on_insert(runner):
    runner.execute("drop table if exists memory.sc_inval")
    runner.execute("create table memory.sc_inval as "
                   "select n_nationkey, n_name from nation")
    q = "select count(*) from memory.sc_inval"
    assert runner.execute(q).rows == [(25,)]
    assert runner.execute(q).rows == [(25,)]          # warm hit
    runner.execute("insert into memory.sc_inval "
                   "select n_nationkey + 100, n_name from nation")
    # the write invalidated the cached split: new rows are visible
    assert runner.execute(q).rows == [(50,)]
    runner.execute("drop table memory.sc_inval")


def test_invalidation_on_sqlite_write(tmp_path):
    import sqlite3
    path = str(tmp_path / "sc.db")
    db = sqlite3.connect(path)
    db.execute("create table t (a INTEGER)")
    db.executemany("insert into t values (?)", [(i,) for i in range(10)])
    db.commit()
    from presto_tpu.connectors.sqlite import SqliteConnector
    conn = SqliteConnector(path)
    catalogs = CatalogManager()
    catalogs.register("db", conn)
    r = LocalRunner(catalogs=catalogs, catalog="db")
    q = "select count(*) from t"
    assert r.execute(q).rows[0][0] == 10
    assert r.execute(q).rows[0][0] == 10              # warm hit
    # a write THROUGH the connector invalidates (same path as its
    # TableStats cache)
    r.execute("insert into t select a + 10 from t")
    assert r.execute(q).rows[0][0] == 20


# -- eviction under a small limit --------------------------------------------

class _Obj:
    pass


def _mini_batch(n=64):
    return Batch.from_pydict({"x": (T.BIGINT, list(range(n)))})


def test_eviction_under_small_limit():
    b = _mini_batch()
    from presto_tpu.memory import batch_device_bytes
    nbytes = batch_device_bytes(b)
    cache = ScanCache(limit_bytes=int(nbytes * 2.5))  # fits two entries
    conn = _Obj()
    th = TableHandle("c", "s", "t")
    evicted0 = _counter("scan_cache_evicted_bytes_total")
    keys = [ScanCache.key(conn, "c", Split(th, (i,)), ("x",), None, 0)
            for i in range(3)]
    for k in keys:
        assert cache.put(k, conn, [b])
    # third insert evicted the LRU (first) entry
    assert len(cache) == 2
    assert cache.resident_bytes <= cache.pool.limit
    assert _counter("scan_cache_evicted_bytes_total") >= evicted0 + nbytes
    assert cache.get(keys[0], conn) is None           # evicted
    assert cache.get(keys[2], conn) is not None
    # an entry that can never fit is refused outright
    big = ScanCache(limit_bytes=nbytes // 2)
    assert not big.put(keys[0], conn, [b])
    assert len(big) == 0


def test_put_refused_after_version_bump():
    """A write landing while a scan decodes must not let the scan park
    a stale (unreachable) entry under the pre-write version."""
    b = _mini_batch()
    cache = ScanCache(limit_bytes=1 << 20)

    class _Versioned:
        v = 1

        def data_version(self, table):
            return self.v

    conn = _Versioned()
    th = TableHandle("c", "s", "t")
    key = ScanCache.key(conn, "c", Split(th, (0,)), ("x",), None,
                        conn.data_version("t"))
    conn.v = 2            # concurrent write bumped the version
    assert not cache.put(key, conn, [b])
    assert len(cache) == 0 and cache.resident_bytes == 0


def test_shrinking_limit_evicts():
    b = _mini_batch()
    from presto_tpu.memory import batch_device_bytes
    nbytes = batch_device_bytes(b)
    cache = ScanCache(limit_bytes=nbytes * 4)
    conn = _Obj()
    th = TableHandle("c", "s", "t")
    for i in range(3):
        cache.put(ScanCache.key(conn, "c", Split(th, (i,)), ("x",),
                                None, 0), conn, [b])
    assert len(cache) == 3
    cache.set_limit(nbytes)
    assert len(cache) == 1
    assert cache.resident_bytes <= nbytes


# -- prefetcher ---------------------------------------------------------------

def _scan_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("scan-prefetch")]


def _assert_no_scan_threads():
    deadline = time.time() + 5.0
    while _scan_threads() and time.time() < deadline:
        time.sleep(0.02)
    assert not _scan_threads()


def test_prefetcher_shutdown_clean(runner):
    # full drain
    runner.execute("select count(*) from lineitem")
    _assert_no_scan_threads()
    # early abandonment (LIMIT satisfied before the scan finishes)
    runner.execute("select l_orderkey from lineitem limit 3",
                   properties={"scan_threads": 2, "scan_cache": False})
    _assert_no_scan_threads()


class _SlowSource(PageSource):
    def __init__(self, batches, delay_s):
        self._batches = batches
        self._delay = delay_s

    def batches(self):
        for b in self._batches:
            time.sleep(self._delay)
            yield b


class _SlowMeta(ConnectorMetadata):
    def __init__(self, schema):
        self._schema = schema

    def list_tables(self, schema=None):
        return ["slow"]

    def table_schema(self, table):
        return self._schema


class _SlowSplits(ConnectorSplitManager):
    def __init__(self, n):
        self.n = n

    def splits(self, table, desired=1):
        return [Split(table, (i,)) for i in range(self.n)]


class _SlowConnector(Connector):
    """Fixed table, n splits, ``delay_s`` of fake decode per batch."""

    name = "slow"

    def __init__(self, n_splits=4, delay_s=0.05):
        self._batch = Batch.from_pydict(
            {"x": (T.BIGINT, list(range(128)))})
        self._meta = _SlowMeta(self._batch.schema)
        self._splits = _SlowSplits(n_splits)
        self.delay_s = delay_s

    @property
    def metadata(self):
        return self._meta

    @property
    def split_manager(self):
        return self._splits

    def data_version(self, table):
        return 0

    def page_source(self, split, columns, pushdown=None,
                    rows_per_batch=1 << 17):
        return _SlowSource([self._batch.select(list(columns))],
                           self.delay_s)


def test_warm_measurably_faster_than_cold():
    """The committed warm-vs-cold check: a decode-bound scan's re-run
    must not pay the decode again (device-resident replay)."""
    catalogs = CatalogManager()
    catalogs.register("slow", _SlowConnector(n_splits=4, delay_s=0.1))
    r = LocalRunner(catalogs=catalogs, catalog="slow")
    q = "select count(*) from slow"
    t0 = time.perf_counter()
    cold = r.execute(q).rows
    cold_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    warm = r.execute(q).rows
    warm_s = time.perf_counter() - t1
    assert warm == cold == [(512,)]
    assert cold_s >= 0.2          # 4 splits x 0.1s over 2 threads
    assert warm_s < cold_s * 0.5  # warm replay skips the decode wall


def test_prefetch_overlaps_decode():
    """With prefetch ON, 2 workers overlap split decodes; serially the
    same scan pays the full decode sum."""
    conn = _SlowConnector(n_splits=4, delay_s=0.1)
    th = TableHandle("slow", "default", "slow")
    splits = conn.split_manager.splits(th, 4)

    def drain(opts):
        t0 = time.perf_counter()
        n = sum(b.host_count()
                for b in scancache.scan_splits(
                    conn, "slow", ["x"], splits, lambda: None, 1 << 17,
                    opts))
        return n, time.perf_counter() - t0

    n1, serial_s = drain(ScanOptions(cache=False, prefetch=False))
    n2, overlap_s = drain(ScanOptions(cache=False, prefetch=True,
                                      threads=4, depth=2))
    assert n1 == n2 == 512
    assert serial_s >= 0.4
    assert overlap_s < serial_s * 0.75


# -- ragged-split padding -----------------------------------------------------

def test_ragged_final_chunk_padded():
    conn = TpchConnector(sf=SF)
    th = TableHandle("tpch", "default", "orders")
    splits = conn.split_manager.splits(th, 1)
    # rows_per_batch deliberately NOT a power of two: full chunks bucket
    # to 16384; the residual would bucket smaller without padding
    rpb = 10_000
    padded = list(scancache.scan_splits(
        conn, "tpch", ["o_orderkey"], splits, lambda: None, rpb,
        ScanOptions(cache=False, prefetch=False, pad=True)))
    assert len(padded) > 1
    assert len({b.capacity for b in padded}) == 1     # one bucket, one
    #                                                   executable
    raw = list(scancache.scan_splits(
        conn, "tpch", ["o_orderkey"], splits, lambda: None, rpb,
        ScanOptions(cache=False, prefetch=False, pad=False)))
    assert raw[-1].capacity < raw[0].capacity          # ragged without
    assert sum(b.host_count() for b in padded) == \
        sum(b.host_count() for b in raw)               # same live rows


# -- observability ------------------------------------------------------------

def test_metrics_surfaces(runner):
    runner.execute("select count(*) from region")
    runner.execute("select count(*) from region")
    rows = runner.execute(
        "select name, value from system.runtime.metrics "
        "where name like 'scan_cache%'").rows
    names = {r[0] for r in rows}
    assert {"scan_cache_hit_total", "scan_cache_miss_total",
            "scan_cache_evicted_bytes_total",
            "scan_cache_resident_bytes"} <= names
    by_name = {r[0]: r[1] for r in rows}
    assert by_name["scan_cache_resident_bytes"] > 0
    from presto_tpu.obs.exposition import render_exposition
    text = render_exposition(REGISTRY)
    assert "scan_cache_hit_total" in text
    assert "scan_prefetch_stall_seconds" in text


def test_explain_analyze_scan_cache_line(runner):
    runner.execute("select count(*) from supplier")
    out = runner.execute("explain analyze select count(*) from supplier")
    text = "\n".join(r[0] for r in out.rows)
    assert "Scan cache:" in text
    assert "hit" in text.split("Scan cache:")[1]


def test_a_narrower_scan_is_served_from_the_wider_entry():
    """TPC-H Q21 reads lineitem three times, twice four columns and
    once two of them: ONE resident copy serves all three (ISSUE 35). A
    scan whose columns an entry of the same split holds is a hit on
    that entry, narrowed on the host; a wider scan that arrives later
    replaces what it makes redundant."""
    from presto_tpu.exec.scancache import CACHE
    from presto_tpu.obs.metrics import REGISTRY
    CACHE.clear()
    r = LocalRunner(tpch_sf=0.01, rows_per_batch=8192)
    wide = "select sum(l_suppkey), max(l_commitdate) from lineitem " \
        "where l_receiptdate > l_commitdate and l_orderkey > 0"
    narrow = "select sum(l_suppkey), count(l_orderkey) from lineitem"
    want_narrow = r.execute(narrow).rows          # staged: two columns
    resident = CACHE.resident_bytes
    want_wide = r.execute(wide).rows              # four: replaces the two
    assert resident < CACHE.resident_bytes < 3 * resident
    with CACHE._lock:
        columns = {k[CACHE._COLUMNS] for k in CACHE._entries
                   if k[3] == "lineitem"}
    assert len(columns) == 1 and len(next(iter(columns))) == 4
    hits = REGISTRY.value("scan_cache_hit_total")
    misses = REGISTRY.value("scan_cache_miss_total")
    held = CACHE.resident_bytes
    assert r.execute(narrow).rows == want_narrow
    assert r.execute(wide).rows == want_wide
    assert REGISTRY.value("scan_cache_miss_total") == misses
    assert REGISTRY.value("scan_cache_hit_total") > hits
    assert CACHE.resident_bytes == held
