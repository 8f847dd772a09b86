"""Benchmark entry: TPC-H operator-pipeline throughput on device.

Mirrors the reference's operator benchmark metric (reference
presto-benchmark/.../AbstractOperatorBenchmark.java:303-330 reports
input_rows_per_second over hand-built operator pipelines,
HandTpchQuery1.java / HandTpchQuery6.java). Staged configs
(BASELINE.md): Q6 @ SF1 (scan-filter-agg), Q1 @ SF10 (group-by
aggregation), Q3 @ SF10 (3-way join + high-cardinality group-by + top-n;
set BENCH_SF_Q3=100 for the full-scale config when wall-clock allows),
and TPC-DS q55/q27 @ SF1 (star joins + ROLLUP, BASELINE config 4; the
engine runs the full SQL path — parse/plan/optimize/execute — while the
proxy computes the identical query; set BENCH_SF_DS to rescale).

Baseline: the reference publishes no absolute numbers and no JVM exists
in this image (BASELINE.md requires measuring the Java harness; `which
java` is empty here), so `vs_baseline` is measured against a vectorized
NumPy implementation of the IDENTICAL pipeline over the IDENTICAL
pre-generated chunks on this host (single core, like one Presto driver
thread). The proxy favors the baseline: NumPy's C kernels are at least
as fast per core as Presto's Java operator loops (whose PageProcessor
makes per-row virtual calls per column), so the reported ratio is a
LOWER bound on the vs-Java speedup per core.

Input generation is excluded from both sides' timing (both sides would
share the same host generator; the reference harness likewise reads
pre-staged in-memory pages). Device timing covers all compute plus the
final result readback; input staging is untimed on both sides.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline",
"sub_metrics": [...]}.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from tpch_reference import (D_Q1, D_Q3, Q1_COLS as _Q1_COLS, q1_numpy_rows,
                            q1_numpy_sums, q3_numpy, q6_numpy, stage_host)


def _enable_compile_cache() -> None:
    from presto_tpu import enable_compile_cache
    enable_compile_cache()


def _stage(conn, table, cols, rows_per_batch, device: bool):
    """Generate a table's chunks once (tpch_reference.stage_host). Host
    copies keep the chunked shape (one chunk = one Presto page for the
    NumPy baseline); the device copy is a few large batches per table —
    one transfer and one kernel launch per 2^23 rows instead of one per
    generated chunk (not measured on the v5e)."""
    from presto_tpu.batch import Batch

    host, n, schema, dicts = stage_host(conn, table, cols, rows_per_batch)
    dev = []
    if device:
        # chunk the device copy at 2^23 rows: reuses one compiled
        # kernel, pipelines dispatch and caps HBM peaks (a single
        # 2^26-capacity batch through the combined filter+8-agg kernel
        # is not measured on the v5e)
        chunk_rows = 1 << 23
        arrays = [np.concatenate([h[i] for h in host])
                  for i in range(len(cols))]
        for lo in range(0, n, chunk_rows):
            cn = min(chunk_rows, n - lo)
            dev.append(Batch.from_arrays(
                schema, [a[lo:lo + cn] for a in arrays],
                dictionaries=dicts, num_rows=cn))
    return dev, host, n, schema, dicts


def _time(fn):
    fn()                            # warmup + compile
    t0 = time.perf_counter()
    got = fn()
    return got, time.perf_counter() - t0


#: proxy repetitions for the CURRENT config — set by main() per config:
#: 1 when a pinned proxy time exists (the pin carries the ratio), else 3
_PROXY_RUNS = 3


def _time_proxy(fn):
    """Warmup + best-of-N wall clock for the NumPy proxy. The proxy runs
    on a SHARED host: a contention spike on one run used to swing
    `vs_baseline` 2-3x between rounds (docs/perf.md) — min-of-N rejects
    the spikes, and main() additionally pins the first clean measurement
    in BASELINE_PROXY.json so later rounds' gate numbers move only when
    the ENGINE moves."""
    got, best = _time(fn)
    for _ in range(max(0, _PROXY_RUNS - 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return got, best


_PROXY_PIN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BASELINE_PROXY.json")


def _load_proxy_pins() -> dict:
    try:
        with open(_PROXY_PIN_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _pin_proxy_seconds(metric: str, measured: float) -> float:
    """Proxy-seconds used for the gate ratio: the committed pin when one
    exists (so the ratio can't swing with host contention), else the
    fresh measurement — which is then written back so the NEXT run is
    pinned. BENCH_REPIN=1 forces re-measurement to take over the pin."""
    pins = _load_proxy_pins()
    if metric in pins and not os.environ.get("BENCH_REPIN"):
        return float(pins[metric])
    pins[metric] = round(measured, 4)
    try:
        with open(_PROXY_PIN_PATH, "w") as f:
            json.dump(pins, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return measured


# ---------------------------------------------------------------------------
# Q6: scan-filter-aggregate (reference HandTpchQuery6.java)
# ---------------------------------------------------------------------------

def bench_q6(sf: float):
    import jax
    import jax.numpy as jnp
    from presto_tpu import types as T
    from presto_tpu.expr.compiler import compile_filter, compile_projection
    from presto_tpu.ops.aggregation import AggSpec, global_aggregate
    import __graft_entry__ as ge

    conn = _shared_tpch(sf)
    dev, host, total, _, _ = _stage(conn, "lineitem", ge._Q6_COLS,
                                    1 << 20, True)

    schema, pred, proj = ge._q6_exprs()
    filt = compile_filter(pred, schema)
    project = compile_projection(proj, ["rev"], schema)
    aggs = [AggSpec("sum", 0, T.DOUBLE, "revenue")]

    @jax.jit
    def q6_partial(batch):
        p = global_aggregate(project(filt(batch)), aggs, mode="partial")
        return p.columns[0].data[0]

    combine = jax.jit(lambda vs: jnp.sum(jnp.stack(vs)))

    def run_device():
        # async dispatch per batch; sync exactly once at the final scalar
        return float(combine([q6_partial(b) for b in dev]))

    def run_numpy():
        return q6_numpy(host)

    got, dev_s = _time(run_device)
    want, np_s = _time_proxy(run_numpy)
    assert abs(got - want) <= 1e-8 * max(abs(want), 1.0), (got, want)
    return total, dev_s, np_s


# ---------------------------------------------------------------------------
# Q1: group-by aggregation (reference HandTpchQuery1.java)
# ---------------------------------------------------------------------------

def bench_q1(sf: float):
    import jax
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Column, Schema, concat_batches
    from presto_tpu.ops.aggregation import AggSpec, grouped_aggregate

    conn = _shared_tpch(sf)
    dev, host, total, schema, _ = _stage(conn, "lineitem", _Q1_COLS,
                                         1 << 20, True)
    rf_vocab = dev[0].columns[0].dictionary
    ls_vocab = dev[0].columns[1].dictionary

    aggs = [
        AggSpec("sum", 2, T.DOUBLE, "sum_qty"),
        AggSpec("sum", 3, T.DOUBLE, "sum_base"),
        AggSpec("sum", 7, T.DOUBLE, "sum_disc_price"),
        AggSpec("sum", 8, T.DOUBLE, "sum_charge"),
        AggSpec("avg", 2, T.DOUBLE, "avg_qty"),
        AggSpec("avg", 3, T.DOUBLE, "avg_price"),
        AggSpec("avg", 4, T.DOUBLE, "avg_disc"),
        AggSpec("count_star", None, T.BIGINT, "count_order"),
    ]
    ext_schema = Schema(list(zip(schema.names, schema.types)) + [
        ("disc_price", T.DOUBLE), ("charge", T.DOUBLE)])

    @jax.jit
    def q1_partial(b: Batch) -> Batch:
        mask = b.row_mask & (b.columns[6].data <= D_Q1)
        price, disc, tax = (b.columns[i].data for i in (3, 4, 5))
        disc_price = price * (1.0 - disc)
        charge = disc_price * (1.0 + tax)
        valid = b.columns[3].validity & b.columns[4].validity
        cols = list(b.columns) + [
            Column(T.DOUBLE, disc_price, valid, None),
            Column(T.DOUBLE, charge, valid & b.columns[5].validity, None),
        ]
        ext = Batch(ext_schema, cols, mask)
        # <= 12 possible (returnflag, linestatus) slots: emit the partial
        # straight at 128-slot capacity — materializing a partial at the
        # 2^26 input capacity (13 state cols x 67M x 8B ~ 7GB) OOMs HBM
        # at SF10, which is what killed the round-2 bench
        return grouped_aggregate(ext, [0, 1], aggs, mode="partial",
                                 output_capacity=128)

    @jax.jit
    def q1_final(parts):
        states = concat_batches(parts, capacity=128 * len(parts))
        return grouped_aggregate(states, [0, 1], aggs, mode="final")

    def run_device():
        import jax.numpy as jnp
        out = q1_final([q1_partial(b) for b in dev])
        # scalar readback: forces the whole chain and pays one result
        # delivery, like the other configs' result readbacks
        float(jnp.sum(out.columns[2].data))
        return out

    def run_numpy():
        return q1_numpy_sums(host, len(rf_vocab), len(ls_vocab))

    out, dev_s = _time(run_device)
    want, np_s = _time_proxy(run_numpy)
    got = {(rf_vocab.index(r[0]), ls_vocab.index(r[1])): r[2:]
           for r in out.to_pylist()}
    assert set(got) == set(want), (sorted(got), sorted(want))
    for k, acc in want.items():
        g = got[k]
        for gv, wv in zip(g[:4], acc[:4]):
            assert abs(gv - wv) <= 1e-6 * max(abs(wv), 1.0), (k, g, acc)
        assert g[7] == int(acc[5]), (k, g, acc)
    return total, dev_s, np_s


# ---------------------------------------------------------------------------
# Q3: 3-way join + group-by + top-n (reference
# HashBuildAndJoinBenchmark.java shape with HandTpchQuery-style agg)
# ---------------------------------------------------------------------------

def bench_q3(sf: float):
    """Q3 device plan: eager aggregation pushed through the join.

    The grouping key (l_orderkey) IS the join key and o_orderkey is
    unique, so revenue partials can be aggregated on the probe side
    BEFORE the join (the reference's
    iterative/rule/PushPartialAggregationThroughJoin.java rewrite) into
    a direct-address slot table over the o_orderkey span (reference
    BigintGroupByHash.java's dense-int mode). The join then degenerates
    to ONE gather per filtered order — no sort, no per-chunk group-by,
    no probe binary search. Exact sums come from i32 digit scatters
    (ops/scatter_agg.py): f64/i64 scatters are ~14x slower on this chip.
    TPC-H spec: at most 7 lineitems per order, so i32 digit sums cannot
    overflow (w=28: 2^28 * 7 < 2^31)."""
    import jax
    import jax.numpy as jnp
    from presto_tpu.batch import Batch, bucket_capacity, concat_batches
    from presto_tpu.ops.scatter_agg import segment_sum_exact

    conn = _shared_tpch(sf)
    li_cols = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
    o_cols = ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]
    c_cols = ["c_custkey", "c_mktsegment"]
    # lineitem beyond ~SF20 would not fit on one chip: stream from host
    li_device = sf <= 20
    li_dev, li_host, n_li, li_schema, _ = _stage(conn, "lineitem",
                                                 li_cols, 1 << 20,
                                                 li_device)
    o_dev, o_host, n_o, _, _ = _stage(conn, "orders", o_cols, 1 << 20,
                                      True)
    c_dev, c_host, n_c, _, _ = _stage(conn, "customer", c_cols, 1 << 20,
                                      True)
    total = n_li + n_o + n_c
    seg_code = c_dev[0].columns[1].dictionary.index("BUILDING")

    orders = concat_batches(o_dev) if len(o_dev) > 1 else o_dev[0]
    customer = concat_batches(c_dev) if len(c_dev) > 1 else c_dev[0]

    @jax.jit
    def all_key_bounds(orders: Batch, customer: Batch):
        out = []
        for b in (orders, customer):
            k = b.columns[0].data
            live = b.row_mask & b.columns[0].validity
            out.append(jnp.min(jnp.where(live, k,
                                         jnp.iinfo(jnp.int64).max)))
            out.append(jnp.max(jnp.where(live, k,
                                         jnp.iinfo(jnp.int64).min)))
        return jnp.stack(out)

    def partial_fn(ok_lo, ok_cap):
        @jax.jit
        def partial(li: Batch, acc):
            # shipdate filter + revenue in 4-decimal fixed point (exact:
            # price/discount are 2-decimal quantities)
            lmask = li.row_mask & (li.columns[3].data > D_Q3)
            price, disc = li.columns[1].data, li.columns[2].data
            rev_int = jnp.round(price * (1.0 - disc) * 1e4).astype(
                jnp.int64)
            slot = jnp.clip(li.columns[0].data - ok_lo, 0,
                            ok_cap - 1).astype(jnp.int32)
            vals = jnp.where(lmask, rev_int, 0)
            # l_orderkey is physically ascending within a staged chunk
            return acc + segment_sum_exact(
                vals, slot, ok_cap, max_rows_per_segment=7,
                value_bits=31, indices_are_sorted=True)
        return partial

    def finalize_fn(ok_lo, ok_cap, c_lo, c_cap):
        @jax.jit
        def finalize(orders: Batch, customer: Batch, acc):
            # customer BUILDING membership as a direct-address bool table
            c_slot = jnp.clip(customer.columns[0].data - c_lo, 0,
                              c_cap - 1).astype(jnp.int32)
            c_building = (customer.row_mask & customer.columns[0].validity
                          & (customer.columns[1].data == seg_code))
            seg_table = jnp.zeros(c_cap, dtype=bool).at[c_slot].max(
                c_building)
            ok, ocust = orders.columns[0].data, orders.columns[1].data
            odate = orders.columns[2].data.astype(jnp.int64)
            oprio = orders.columns[3].data
            o_live = (orders.row_mask & (odate < D_Q3)
                      & jnp.take(seg_table,
                                 jnp.clip(ocust - c_lo, 0, c_cap - 1)
                                 .astype(jnp.int32), axis=0))
            # the pushed-down join: one gather of the revenue slot table
            rev_int = jnp.take(acc, jnp.clip(ok - ok_lo, 0, ok_cap - 1)
                               .astype(jnp.int32), axis=0)
            cand = o_live & (rev_int > 0)
            # ORDER BY revenue DESC, o_orderdate ASC as one packed i64:
            # rev_int < 2^43 and epoch-day < 2^15
            key = jnp.where(cand, rev_int * (1 << 15) + (32767 - odate),
                            -1)
            top, idx = jax.lax.top_k(key, 10)
            gather = lambda a: jnp.take(a, idx, axis=0)
            return (top, gather(ok), gather(rev_int), gather(odate),
                    gather(oprio))
        return finalize

    def device_chunks():
        if li_device:
            yield from li_dev
            return
        for c in li_host:
            arrays, mask = c[:-1], c[-1]
            yield Batch.from_arrays(li_schema, list(arrays),
                                    num_rows=int(mask.sum()))

    def run_device():
        bounds = [int(v) for v in all_key_bounds(orders, customer)]
        ok_lo, ok_hi, c_lo, c_hi = bounds             # one host sync
        ok_cap = bucket_capacity(max(ok_hi - ok_lo + 1, 1))
        c_cap = bucket_capacity(max(c_hi - c_lo + 1, 1))
        partial = partial_fn(ok_lo, ok_cap)
        finalize = finalize_fn(ok_lo, ok_cap, c_lo, c_cap)
        acc = jnp.zeros(ok_cap, dtype=jnp.int64)
        for b in device_chunks():
            acc = partial(b, acc)
        top, ok, rev_int, odate, oprio = (
            np.asarray(v) for v in finalize(orders, customer, acc))
        return [(int(k), int(r) / 1e4, int(d), int(p))
                for t, k, r, d, p in zip(top, ok, rev_int, odate, oprio)
                if t >= 0]

    def run_numpy():
        return q3_numpy(c_host, o_host, li_host, seg_code)

    got, dev_s = _time(run_device)
    want, np_s = _time_proxy(run_numpy)
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and abs(g[1] - w[1]) <= 1e-6 * abs(w[1]), (g, w)
    return total, dev_s, np_s


# ---------------------------------------------------------------------------
# Q1 through the ENGINE SQL path: parse -> plan -> optimize -> execute.
# The hand pipeline above proves the kernels; this config makes the
# planner/executor overhead on TPC-H visible to the gate (VERDICT.md
# weak point 2 — previously only the TPC-DS configs exercised it).
# ---------------------------------------------------------------------------

_TPCH_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""


def bench_q1sql(sf: float):
    conn = _shared_tpch(sf)
    runner = _shared_runner("tpch", sf)
    _, host, total, _, vocabs = _stage(conn, "lineitem", _Q1_COLS,
                                       1 << 20, False)
    rf_vocab, ls_vocab = vocabs[0], vocabs[1]

    def run_engine():
        return runner.execute(_TPCH_Q1).rows

    def run_numpy():
        return q1_numpy_rows(host, rf_vocab, ls_vocab)

    got, dev_s = _time(run_engine)
    want, np_s = _time_proxy(run_numpy)
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        assert (str(g[0]), str(g[1])) == (w[0], w[1]), (g, w)
        for gv, wv in zip(g[2:9], w[2:9]):
            assert abs(float(gv) - wv) <= 1e-6 * max(abs(wv), 1.0), (g, w)
        assert int(g[9]) == w[9], (g, w)
    return total, dev_s, np_s


# ---------------------------------------------------------------------------
# TPC-DS q55 / q27 (BASELINE config 4): macro SQL benchmark, engine vs a
# vectorized NumPy implementation of the identical query over the identical
# pre-staged data (reference presto-benchto-benchmarks/.../tpcds/q55.sql,
# q27.sql; macro metric per PrestoBenchmarkDriver = query wall-clock).
# ---------------------------------------------------------------------------

_DS_Q55 = """
select i_brand_id brand_id, i_brand brand,
       sum(ss_ext_sales_price) ext_price
from date_dim, store_sales, item
where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
  and i_manager_id = 28 and d_moy = 11 and d_year = 1999
group by i_brand, i_brand_id
order by ext_price desc, i_brand_id
limit 100
"""

_DS_Q27 = """
select i_item_id, s_state, grouping(s_state) g_state,
       avg(ss_quantity) agg1, avg(ss_list_price) agg2,
       avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4
from store_sales, customer_demographics, date_dim, store, item
where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
  and ss_store_sk = s_store_sk and ss_cdemo_sk = cd_demo_sk
  and cd_gender = 'M' and cd_marital_status = 'S'
  and cd_education_status = 'College' and d_year = 2002
  and s_state in ('TN', 'TN', 'TN', 'TN', 'TN', 'TN')
group by rollup (i_item_id, s_state)
order by i_item_id nulls last, s_state nulls last
limit 100
"""


#: shared connector/runner instances across query configs: q55 and q27
#: used to each rebuild the SF10 TPC-DS dataset from scratch (~230s of
#: wall per config, mostly datagen); one TpcdsConnector + one engine
#: runner per scale factor means the tables generate once, and the
#: engine-side device scan cache (exec/scancache.py) carries hot split
#: data from one config's warmup into the next config's run
_SHARED_CONNS: dict = {}
_SHARED_RUNNERS: dict = {}


def _shared_tpch(sf: float):
    from presto_tpu.connectors.tpch import TpchConnector
    key = ("tpch", sf)
    if key not in _SHARED_CONNS:
        _SHARED_CONNS[key] = TpchConnector(sf=sf)
    return _SHARED_CONNS[key]


def _shared_tpcds(sf: float):
    from presto_tpu.connectors.tpcds import TpcdsConnector
    key = ("tpcds", sf)
    if key not in _SHARED_CONNS:
        _SHARED_CONNS[key] = TpcdsConnector(sf=sf)
    return _SHARED_CONNS[key]


def _shared_runner(catalog: str, sf: float):
    """One LocalRunner per (catalog, sf), mounted over the shared
    connector; the device scan cache persists across configs so the
    engine's timed runs read device-resident pages — the same footing
    as the NumPy proxy and the reference harness
    (AbstractOperatorBenchmark reads pre-staged in-memory pages)."""
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.exec.runner import LocalRunner
    key = (catalog, sf)
    if key not in _SHARED_RUNNERS:
        conn = (_shared_tpch(sf) if catalog == "tpch"
                else _shared_tpcds(sf))
        catalogs = CatalogManager()
        catalogs.register(catalog, conn)
        # 2^22-row scan batches for the TPC-DS macro configs: the
        # device-resident scan cache makes big batches free on re-runs
        # (no host re-decode per query), and 4x fewer batches means 4x
        # fewer per-batch dispatches and fused-chain liveness syncs
        # (what a dispatch or a sync costs is not measured on the
        # v5e). Stays 2x under the 2^23 staging chunks the hand
        # configs already use.
        rpb = (1 << 22) if catalog == "tpcds" else (1 << 20)
        runner = LocalRunner(catalogs=catalogs, catalog=catalog,
                             rows_per_batch=rpb)
        # SF10 q1sql/q27 column sets run ~2-3.5GB of decoded device
        # columns each; the default 2GB cap would thrash between
        # configs (the limit is process-wide, so set it on the cache —
        # it is deliberately not a session property)
        from presto_tpu.exec.scancache import CACHE
        CACHE.set_limit(6 << 30)
        # 4 scan threads: 4-way split datagen/decode overlap on the
        # cold pass (the warm pass reads the cache either way)
        runner.session.properties["scan_threads"] = 4
        _SHARED_RUNNERS[key] = runner
    return _SHARED_RUNNERS[key]


#: per-table UNION of every proxy config's columns, so one generation
#: pass serves both q55 and q27 (the raw arrays cache undecoded;
#: dictionary decode happens per request below)
_DS_PROXY_COLS = {
    "date_dim": ("d_date_sk", "d_moy", "d_year"),
    "item": ("i_item_sk", "i_item_id", "i_brand_id", "i_brand",
             "i_manager_id"),
    "store": ("s_store_sk", "s_state"),
    "customer_demographics": ("cd_demo_sk", "cd_gender",
                              "cd_marital_status",
                              "cd_education_status"),
    "store_sales": ("ss_sold_date_sk", "ss_item_sk",
                    "ss_ext_sales_price", "ss_cdemo_sk", "ss_store_sk",
                    "ss_quantity", "ss_list_price", "ss_coupon_amt",
                    "ss_sales_price"),
}
_NP_COLS_CACHE: dict = {}


def _np_cols(conn, table, cols, decode=()):
    """One table's columns as host numpy arrays (dict columns decoded to
    object arrays when listed in ``decode``), generated host-side ONCE
    per (connector, table) — the union of every config's columns — and
    served from cache thereafter."""
    from presto_tpu.connectors.spi import TableHandle

    key = (id(conn), table)
    got = _NP_COLS_CACHE.get(key)
    if got is None:
        gen_cols = list(_DS_PROXY_COLS.get(table, ()))
        for c in cols:
            if c not in gen_cols:
                gen_cols.append(c)
        th = TableHandle("tpcds", "default", table)
        parts = {c: [] for c in gen_cols}
        vocabs: dict = {}
        n = 0
        for split in conn.split_manager.splits(th, 1):
            ps = conn.page_source(split, gen_cols, rows_per_batch=1 << 20)
            for _, data, cn in ps.host_chunks():
                for c in gen_cols:
                    arr, vocab = data[c]
                    parts[c].append(np.asarray(arr))
                    vocabs[c] = vocab
                n += cn
        got = ({c: np.concatenate(v) for c, v in parts.items()},
               vocabs, n)
        _NP_COLS_CACHE[key] = got
    raw, vocabs, n = got
    out = {}
    for c in cols:
        arr = raw[c]
        vocab = vocabs.get(c)
        if c in decode and vocab is not None and vocab != "text":
            arr = np.asarray(tuple(vocab), dtype=object)[arr]
        out[c] = arr
    return out, n


def bench_q55(sf: float):
    conn = _shared_tpcds(sf)
    runner = _shared_runner("tpcds", sf)

    dd, n_dd = _np_cols(conn, "date_dim", ["d_date_sk", "d_moy", "d_year"])
    it, n_it = _np_cols(conn, "item",
                        ["i_item_sk", "i_brand_id", "i_brand",
                         "i_manager_id"], decode=("i_brand",))
    ss, n_ss = _np_cols(conn, "store_sales",
                        ["ss_sold_date_sk", "ss_item_sk",
                         "ss_ext_sales_price"])
    total = n_dd + n_it + n_ss

    def run_engine():
        return runner.execute(_DS_Q55).rows

    def run_numpy():
        dks = np.sort(dd["d_date_sk"][(dd["d_moy"] == 11)
                                      & (dd["d_year"] == 1999)])
        im = it["i_manager_id"] == 28
        iks = it["i_item_sk"][im]
        order = np.argsort(iks, kind="stable")
        iks = iks[order]
        brand_id = it["i_brand_id"][im][order]
        brand = it["i_brand"][im][order]
        m = np.zeros(len(ss["ss_item_sk"]), dtype=bool)
        if len(dks):
            p = np.minimum(np.searchsorted(dks, ss["ss_sold_date_sk"]),
                           len(dks) - 1)
            m = dks[p] == ss["ss_sold_date_sk"]
        if not len(iks):
            return []
        q = np.minimum(np.searchsorted(iks, ss["ss_item_sk"]), len(iks) - 1)
        m &= iks[q] == ss["ss_item_sk"]
        acc = np.zeros(len(iks))
        np.add.at(acc, q[m], np.round(ss["ss_ext_sales_price"][m], 2))
        # group by (brand, brand_id): item_sk -> brand ids may repeat
        keys = {}
        for j in np.nonzero(acc != 0)[0]:
            k = (int(brand_id[j]), str(brand[j]))
            keys[k] = keys.get(k, 0.0) + acc[j]
        rows = sorted(((bid, b, v) for (bid, b), v in keys.items()),
                      key=lambda r: (-r[2], r[0]))[:100]
        return rows

    got, dev_s = _time(run_engine)
    # the scan-cache warm/cold sub-metric (acceptance: warm re-run of a
    # scan-heavy query measurably beats its cold run): the timed run
    # above hit the device-resident cache; one more run with the
    # scan_cache=false escape hatch pays the decode+staging wall again
    # (kernels stay jit-warm, so the delta isolates the input side)
    t0 = time.perf_counter()
    nocache = runner.execute(_DS_Q55,
                             properties={"scan_cache": False}).rows
    nocache_s = time.perf_counter() - t0
    assert nocache == got, "scan_cache=false changed q55 results"
    want, np_s = _time_proxy(run_numpy)
    assert len(got) == len(want), (got[:3], want[:3])
    for g, w in zip(got, want):
        assert int(g[0]) == w[0] and str(g[1]) == w[1], (g, w)
        assert abs(float(g[2]) - w[2]) <= 1e-6 * max(abs(w[2]), 1.0), (g, w)
    return total, dev_s, np_s, {
        "scan_cache_warm_s": round(dev_s, 4),
        "scan_cache_cold_s": round(nocache_s, 4)}


def bench_q27(sf: float):
    conn = _shared_tpcds(sf)
    runner = _shared_runner("tpcds", sf)

    dd, n_dd = _np_cols(conn, "date_dim", ["d_date_sk", "d_year"])
    it, n_it = _np_cols(conn, "item", ["i_item_sk", "i_item_id"],
                        decode=("i_item_id",))
    st, n_st = _np_cols(conn, "store", ["s_store_sk", "s_state"],
                        decode=("s_state",))
    cd, n_cd = _np_cols(conn, "customer_demographics",
                        ["cd_demo_sk", "cd_gender", "cd_marital_status",
                         "cd_education_status"],
                        decode=("cd_gender", "cd_marital_status",
                                "cd_education_status"))
    ss, n_ss = _np_cols(conn, "store_sales",
                        ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                         "ss_store_sk", "ss_quantity", "ss_list_price",
                         "ss_coupon_amt", "ss_sales_price"])
    total = n_dd + n_it + n_st + n_cd + n_ss

    def run_engine():
        return runner.execute(_DS_Q27).rows

    def run_numpy():
        def member_mask(sorted_keys, values):
            if not len(sorted_keys):
                return np.zeros(len(values), dtype=bool)
            p = np.minimum(np.searchsorted(sorted_keys, values),
                           len(sorted_keys) - 1)
            return sorted_keys[p] == values

        dks = np.sort(dd["d_date_sk"][dd["d_year"] == 2002])
        cdm = ((cd["cd_gender"] == "M") & (cd["cd_marital_status"] == "S")
               & (cd["cd_education_status"] == "College"))
        cks = np.sort(cd["cd_demo_sk"][cdm])
        stm = st["s_state"] == "TN"
        sks = st["s_store_sk"][stm]
        s_order = np.argsort(sks, kind="stable")
        sks_sorted = sks[s_order]
        state_by_store = st["s_state"][stm][s_order]
        iks = it["i_item_sk"]
        i_order = np.argsort(iks, kind="stable")
        iks_sorted = iks[i_order]
        iid_by_item = it["i_item_id"][i_order]

        m = (member_mask(dks, ss["ss_sold_date_sk"])
             & member_mask(cks, ss["ss_cdemo_sk"])
             & member_mask(sks_sorted, ss["ss_store_sk"])
             & member_mask(iks_sorted, ss["ss_item_sk"]))
        ii = np.searchsorted(iks_sorted, ss["ss_item_sk"][m])
        si = np.searchsorted(sks_sorted, ss["ss_store_sk"][m])
        measures = np.stack([
            np.round(ss["ss_quantity"][m].astype(np.float64), 2),
            np.round(ss["ss_list_price"][m], 2),
            np.round(ss["ss_coupon_amt"][m], 2),
            np.round(ss["ss_sales_price"][m], 2)], axis=1)

        def agg(keys_tuple):
            groups = {}
            for idx in range(len(ii)):
                k = keys_tuple(idx)
                s, c = groups.setdefault(k, (np.zeros(4), 0))
                groups[k] = (s + measures[idx], c + 1)
            return groups

        rows = []
        g1 = agg(lambda i: (str(iid_by_item[ii[i]]),
                            str(state_by_store[si[i]])))
        for (iid, state), (s, c) in g1.items():
            rows.append((iid, state, 0) + tuple(s / c))
        g2 = agg(lambda i: str(iid_by_item[ii[i]]))
        for iid, (s, c) in g2.items():
            rows.append((iid, None, 1) + tuple(s / c))
        g3 = agg(lambda i: ())
        for _, (s, c) in g3.items():
            rows.append((None, None, 1) + tuple(s / c))
        rows.sort(key=lambda r: ((r[0] is None, r[0]),
                                 (r[1] is None, r[1])))
        return rows[:100]

    got, dev_s = _time(run_engine)
    want, np_s = _time_proxy(run_numpy)
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert (g[0], g[1], int(g[2])) == (w[0], w[1], w[2]), (g, w)
        for gv, wv in zip(g[3:], w[3:]):
            assert abs(float(gv) - wv) <= 1e-6 * max(abs(wv), 1.0), (g, w)
    return total, dev_s, np_s


# ---------------------------------------------------------------------------
# BASELINE config 5: Hive/ORC lineitem — scan-filter-aggregate with
# on-device columnar (RLEv2) decode through the real ORC reader
# (formats/orc_rle.py), the config VERDICT.md round 5 flagged as never
# benchmarked. Slow-tier guarded: the ORC dataset writes once per run
# and the decode path is the cost being measured, so the config only
# joins the tuple under BENCH_ORC=1 (BENCH_SF_ORC rescales; BASELINE.md
# names SF1000 — far beyond this container, like configs 3/4's SF100).
# ---------------------------------------------------------------------------

_ORC_Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""


def bench_q6orc(sf: float):
    import tempfile

    from presto_tpu.batch import Batch
    from presto_tpu.connectors.orc import OrcConnector
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.exec.runner import LocalRunner
    import __graft_entry__ as ge

    import shutil

    src = _shared_tpch(sf)
    _, host, total, schema, _ = _stage(src, "lineitem", ge._Q6_COLS,
                                       1 << 20, False)
    root = tempfile.mkdtemp(prefix="bench_orc_")
    try:
        conn = OrcConnector(root)
        conn.create_table("lineitem", schema)
        for chunk in host:
            arrays, mask = chunk[:-1], chunk[-1]
            conn.append("lineitem", Batch.from_arrays(
                schema, list(arrays), num_rows=int(mask.sum())))
        catalogs = CatalogManager()
        catalogs.register("orc", conn)
        runner = LocalRunner(catalogs=catalogs, catalog="orc",
                             rows_per_batch=1 << 20)
        # the decode path IS the measurement: the device scan cache
        # would serve the warm (timed) run without touching the reader
        runner.session.properties["scan_cache"] = False

        def run_engine():
            return float(runner.execute(_ORC_Q6).rows[0][0])

        def run_numpy():
            acc = 0.0
            for ship, disc, qty, price, mask in host:
                disc2, qty2, price2 = (np.round(c, 2)
                                       for c in (disc, qty, price))
                m = (mask & (ship >= 8766) & (ship < 9131)
                     & (disc2 >= 0.05) & (disc2 <= 0.07)
                     & (qty2 < 24.0))
                acc += float(np.sum(np.where(m, price2 * disc2, 0.0)))
            return acc

        got, dev_s = _time(run_engine)
        want, np_s = _time_proxy(run_numpy)
        assert abs(got - want) <= 1e-6 * max(abs(want), 1.0), (got, want)
        return total, dev_s, np_s
    finally:
        # a GB-scale dataset per run must not accumulate across rounds
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Serving: concurrent-throughput axis (ROADMAP item 3). N concurrent
# protocol clients drive a mix of repeated parameterized statements
# through a real PrestoTpuServer (resource groups, plan cache, shared
# scans) — the axis every other config ignores: queries/sec under
# multi-tenant load, not one query's wall-clock. Run via
# `python bench.py serving` (or BENCH_SERVING=1); writes the summary to
# SERVING_OUT (default stdout only). tools/check_bench_regression.py
# gates it against the committed SERVING_r*.json.
# ---------------------------------------------------------------------------

#: the repeated-statement mix: dashboard-shaped parameterized queries —
#: a handful of distinct shapes, each fired many times (the plan cache's
#: steady-state case), plus EXECUTE-driven prepared statements
_SERVING_STATEMENTS = [
    "select count(*), sum(l_extendedprice) from lineitem "
    "where l_quantity > {q}",
    "select l_returnflag, count(*) from lineitem "
    "where l_discount between 0.0{d} and 0.08 group by l_returnflag "
    "order by l_returnflag",
    "select o_orderpriority, count(*) from orders "
    "where o_totalprice > {p} group by o_orderpriority "
    "order by o_orderpriority",
    "select n_name, count(*) from nation group by n_name "
    "order by n_name limit {n}",
]


def _serving_mix(n: int):
    """Deterministic mixed workload: ~4 distinct statement shapes over a
    small parameter domain, so most executions repeat an already-seen
    fingerprint (the dashboard traffic the plan cache exists for)."""
    out = []
    for i in range(n):
        tmpl = _SERVING_STATEMENTS[i % len(_SERVING_STATEMENTS)]
        out.append(tmpl.format(q=10 + (i // 4) % 3, d=1 + (i // 4) % 2,
                               p=1000 * (1 + (i // 4) % 3),
                               n=5 + (i // 4) % 2))
    return out


#: the EXECUTE-fleet mix: two prepared shapes, every client binding its
#: own parameters — the parameter-generic template cache's steady state
#: (one plan + one warm executable set across ALL bindings; each bound
#: fingerprint is distinct, so the result cache stays out of the way)
_SERVING_PREPARES = [
    ("dash_q", "select count(*), sum(l_extendedprice) from lineitem "
               "where l_quantity > ?"),
    ("dash_p", "select o_orderpriority, count(*) from orders "
               "where o_totalprice > ? group by o_orderpriority "
               "order by o_orderpriority"),
]


def _execute_fleet_mix(n: int):
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append(f"execute dash_q using {1 + i % 47}")
        else:
            out.append(f"execute dash_p using {100 * (1 + i % 97)}")
    return out


def _repeated_mix(n: int):
    """The standing-query mix: the SAME four statements over and over
    (dashboard refresh) — after the first executions every request is a
    result-cache hit served from stored host rows."""
    fixed = [_SERVING_STATEMENTS[j].format(q=10, d=1, p=1000, n=5)
             for j in range(len(_SERVING_STATEMENTS))]
    return [fixed[i % len(fixed)] for i in range(n)]


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(p * len(sorted_vals)),
                           len(sorted_vals) - 1)]


def _slo_block(timeseries, slo) -> dict:
    """The summary's ``slo`` block — one schema, one builder
    (presto_tpu/obs/slo.py ``slo_block``; the coordinator serves the
    same document live on GET /v1/slo). Schema is owned by
    tools/slo_report.py — check_bench_regression --kind serving
    validates every pin through it."""
    from presto_tpu.obs.slo import slo_block
    return slo_block(timeseries, slo)


def bench_serving(sf: float = 0.01, clients: int = 16,
                  per_client: int = 8, mixes=("mixed", "execute",
                                              "repeated")):
    """Queries/sec + latency percentiles (overall AND per resource
    group) at ``clients`` concurrent protocol clients, across three
    workload phases:

    - **mixed** (the headline, metric-compatible with SERVING_r01): the
      dashboard statement mix, now served by the full cache stack
      (plan cache + plan templates + result cache);
    - **execute**: the EXECUTE fleet — two prepared statements, every
      client binding its own parameters; measures the parameter-generic
      template cache (hit rate = dep-valid template found minus guard
      fallbacks, over all lookups);
    - **repeated**: the standing-query mix (identical statements over
      and over); measures the versioned result cache.

    Plus the cold/warm probe split (cold pays
    parse+plan+optimize+compile; warm rides the caches).
    ``SERVING_CLIENTS`` / ``SERVING_QUERIES`` / ``SERVING_MIX`` (comma
    list of phases) make re-pins reproducible at any scale."""
    import threading

    from presto_tpu.client import StatementClient
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.exec.runner import LocalRunner
    from presto_tpu.obs.metrics import REGISTRY
    from presto_tpu.obs.slo import SLO
    from presto_tpu.obs.timeseries import TIMESERIES
    from presto_tpu.server.protocol import PrestoTpuServer

    catalogs = CatalogManager()
    catalogs.register("tpch", _shared_tpch(sf))
    runner = LocalRunner(catalogs=catalogs, rows_per_batch=1 << 17)
    # the serving stack under test: parameter-generic templates +
    # versioned result cache on top of the PR 8 plan cache. The mesh
    # auto-router (PR 11) stays at its default — with >1 visible device
    # cold executions shard over the mesh; the summary records whether
    # it engaged.
    runner.session.properties.update({"plan_template_cache": True,
                                      "result_cache": True})
    # both serving tenants declare SLOs (docs/observability.md): the
    # health plane (obs/timeseries.py + obs/slo.py) tracks them live
    # and the summary's ``slo`` block pins objectives + burn timeline.
    # Thresholds are deliberately generous — the pin asserts the plane
    # WORKS (timeline, windowed p95, no spurious pages), not that this
    # machine class is fast.
    _slo_spec = {"latencyTargetMs": 2000, "latencyObjective": 0.95,
                 "availabilityObjective": 0.99}
    srv = PrestoTpuServer(runner, resource_groups={
        "rootGroups": [
            {"name": "serving", "hardConcurrencyLimit": 8,
             "maxQueued": 10_000,
             "subGroups": [
                 {"name": "dash", "hardConcurrencyLimit": 8,
                  "schedulingWeight": 2, "slo": dict(_slo_spec)},
                 {"name": "adhoc", "hardConcurrencyLimit": 8,
                  "schedulingWeight": 1, "slo": dict(_slo_spec)}]}],
        "selectors": [{"user": "dash-.*", "group": "serving.dash"},
                      {"group": "serving.adhoc"}]})
    # dense sampling for the bench's short wall: the 5s default would
    # catch ~2 points per phase; 0.2s gives the burn timeline real
    # resolution. srv.start() installs the tracker + starts the loop.
    TIMESERIES.reset()
    SLO.reset()
    TIMESERIES.configure(sample_interval_s=0.2)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        probe = _SERVING_STATEMENTS[0].format(q=10)

        # cold: first-ever execution pays parse+plan+optimize+jit
        # compile; warm (after the traffic phase): fingerprint hit in
        # the caches + warm executables
        c = StatementClient(base, user="bench")
        t0 = time.perf_counter()
        cold_rows = c.execute(probe).rows
        cold_s = time.perf_counter() - t0

        for name, sql in _SERVING_PREPARES:
            c.execute(f"prepare {name} from {sql}")

        _FAMS = ("plan_cache_", "plan_template_cache_", "result_cache_",
                 "scan_shared_attach_total", "mesh_path_selected_total")

        def snap():
            return {m["name"]: m["value"] for m in REGISTRY.snapshot()
                    if m["name"].startswith(_FAMS)}

        def run_phase(statements):
            """One concurrent phase; returns (overall latencies,
            per-group latencies, wall seconds, metric deltas)."""
            # warmup: one pass over the distinct statements so the
            # timed phase measures steady-state serving, not
            # first-compile
            for s in sorted(set(statements)):
                c.execute(s)
            # phase-edge sample: a toy-scale phase can finish entirely
            # between two 0.2s sampler ticks, leaving the SLO timeline
            # without a single windowed point for it ("degenerate slo
            # block") — flush one sample at phase open and one at phase
            # close so even the smallest run pins real p95 points
            TIMESERIES.sample()
            before = snap()
            latencies = []
            by_group = {"dash": [], "adhoc": []}
            lat_lock = threading.Lock()
            errors = []

            def client_loop(ci: int) -> None:
                group = "dash" if ci % 2 == 0 else "adhoc"
                cl = StatementClient(base, user=f"{group}-{ci}")
                try:
                    for qi in range(per_client):
                        sql = statements[(ci * per_client + qi)
                                         % len(statements)]
                        t = time.perf_counter()
                        cl.execute(sql)
                        dt = time.perf_counter() - t
                        with lat_lock:
                            latencies.append(dt)
                            by_group[group].append(dt)
                except Exception as e:   # surfaced, not lost
                    errors.append(f"client {ci}: {e}")

            threads = [threading.Thread(target=client_loop, args=(i,))
                       for i in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_s = time.perf_counter() - t0
            assert not errors, errors
            TIMESERIES.sample()   # phase-close flush (see phase open)
            after = snap()
            delta = {k: after.get(k, 0.0) - before.get(k, 0.0)
                     for k in after}
            latencies.sort()
            for v in by_group.values():
                v.sort()
            return latencies, by_group, wall_s, delta

        n = clients * per_client
        known = ("mixed", "execute", "repeated")
        bad = [m for m in mixes if m not in known]
        if bad or not mixes:
            raise ValueError(
                f"SERVING_MIX: unknown phase(s) {bad or mixes} — "
                f"choose from {', '.join(known)}")
        phases = {}
        if "mixed" in mixes:
            phases["mixed"] = run_phase(_serving_mix(n))
        if "execute" in mixes:
            phases["execute"] = run_phase(_execute_fleet_mix(n))
        if "repeated" in mixes:
            phases["repeated"] = run_phase(_repeated_mix(n))

        t0 = time.perf_counter()
        warm_rows = c.execute(probe).rows
        warm_s = time.perf_counter() - t0
        assert warm_rows == cold_rows, "warm re-run changed results"

        def rate(d, fam, extra_miss=0.0):
            hits = d.get(f"{fam}_hit_total", 0.0)
            misses = d.get(f"{fam}_miss_total", 0.0) + extra_miss
            return hits / max(hits + misses, 1.0)

        lat, groups, wall_s, delta = phases.get(
            "mixed", next(iter(phases.values())))
        qps = round(len(lat) / wall_s, 2)
        summary = {
            "metric": f"serving_tpch_sf{sf:g}_qps",
            "value": qps,
            "unit": "queries/s",
            "clients": clients,
            "queries": len(lat),
            "p50_ms": round(_pct(lat, 0.50) * 1e3, 2),
            "p95_ms": round(_pct(lat, 0.95) * 1e3, 2),
            "p99_ms": round(_pct(lat, 0.99) * 1e3, 2),
            "groups": {
                g: {"queries": len(v),
                    "p50_ms": round(_pct(v, 0.50) * 1e3, 2),
                    "p95_ms": round(_pct(v, 0.95) * 1e3, 2),
                    "p99_ms": round(_pct(v, 0.99) * 1e3, 2)}
                for g, v in groups.items()},
            "plan_cache_hit_rate": round(rate(delta, "plan_cache"), 4),
            "result_cache_hit_rate": round(
                rate(delta, "result_cache"), 4),
            "shared_scan_attaches": int(
                delta.get("scan_shared_attach_total", 0.0)),
            "mesh_path_selected": int(
                delta.get("mesh_path_selected_total", 0.0)),
            "cold_ms": round(cold_s * 1e3, 2),
            "warm_ms": round(warm_s * 1e3, 2),
            "warm_speedup": round(cold_s / warm_s, 2),
            "sub_metrics": [
                {"metric": f"serving_tpch_sf{sf:g}_p95_latency_ms",
                 "value": round(_pct(lat, 0.95) * 1e3, 2), "unit": "ms"},
                {"metric": f"serving_tpch_sf{sf:g}_warm_speedup",
                 "value": round(cold_s / warm_s, 2), "unit": "x"},
                {"metric": f"serving_tpch_sf{sf:g}_dash_p99_ms",
                 "value": round(_pct(groups["dash"], 0.99) * 1e3, 2),
                 "unit": "ms"},
                {"metric": f"serving_tpch_sf{sf:g}_adhoc_p99_ms",
                 "value": round(_pct(groups["adhoc"], 0.99) * 1e3, 2),
                 "unit": "ms"},
            ],
        }
        if "execute" in phases:
            elat, egroups, ewall, edelta = phases["execute"]
            tpl_hits = edelta.get("plan_template_cache_hit_total", 0.0)
            tpl_miss = edelta.get("plan_template_cache_miss_total", 0.0)
            tpl_fb = edelta.get(
                "plan_template_cache_guard_fallback_total", 0.0)
            tpl_rate = (tpl_hits - tpl_fb) / max(tpl_hits + tpl_miss,
                                                 1.0)
            summary["sub_metrics"] += [
                {"metric": f"serving_tpch_sf{sf:g}_execute_qps",
                 "value": round(len(elat) / ewall, 2),
                 "unit": "queries/s",
                 "p95_ms": round(_pct(elat, 0.95) * 1e3, 2),
                 "p99_ms": round(_pct(elat, 0.99) * 1e3, 2)},
                {"metric": f"serving_tpch_sf{sf:g}_template_hit_rate",
                 "value": round(tpl_rate, 4), "unit": "ratio",
                 "guard_fallbacks": int(tpl_fb)},
            ]
        if "repeated" in phases:
            rlat, rgroups, rwall, rdelta = phases["repeated"]
            summary["sub_metrics"] += [
                {"metric": f"serving_tpch_sf{sf:g}_repeated_qps",
                 "value": round(len(rlat) / rwall, 2),
                 "unit": "queries/s",
                 "p95_ms": round(_pct(rlat, 0.95) * 1e3, 2),
                 "p99_ms": round(_pct(rlat, 0.99) * 1e3, 2)},
                {"metric": f"serving_tpch_sf{sf:g}_result_hit_rate",
                 "value": round(rate(rdelta, "result_cache"), 4),
                 "unit": "ratio",
                 "partials": int(rdelta.get(
                     "result_cache_partial_total", 0.0))},
            ]
        summary["slo"] = _slo_block(TIMESERIES, SLO)
        return summary
    finally:
        TIMESERIES.stop()
        srv.stop()


def bench_serving_fleet(sf: float = 0.01, clients: int = 16,
                        per_client: int = 8,
                        mixes=("mixed", "execute", "repeated"),
                        n_coordinators: int = 3):
    """The horizontal-serving axis (SERVING_r04+): the SAME phases as
    :func:`bench_serving`, served by ``n_coordinators`` coordinator
    SUBPROCESSES (tools/fleet.py) over ONE shared worker pool, with
    every client a round-robin :class:`FleetClient` across the fleet.

    Beyond the classic summary (metric-compatible headline + phase
    sub-metrics + slo block, all aggregated fleet-wide), the summary
    carries a ``fleet`` block pinning what only a fleet can show:

    - per-coordinator QPS during the headline phase, plus the
      aggregate (the horizontal-scaling claim);
    - cache COHERENCE across coordinators: a write through coordinator
      0 must invalidate coordinator 1's warm result-cache entry via the
      bump broadcast (fleet_bump_fold_total observed over the wire),
      and the re-read through coordinator 1 must be row-exact;
    - the coordinator-kill drill: SIGKILL one coordinator mid-phase —
      ZERO failed statements (FleetClient failover) and the survivors
      declare the loss (coordinator_lost_total via staleness grace).

    The ``slo`` block becomes the MERGED multi-coordinator form
    (``coordinators: N``, every objective/timeline row tagged with its
    coordinator) — tools/slo_report.py validates both forms."""
    import tempfile
    import threading

    from presto_tpu.client import FleetClient, StatementClient
    from tools.fleet import launch_fleet

    tmpdir = tempfile.mkdtemp(prefix="fleet_bench_")
    sqlite_path = os.path.join(tmpdir, "fleet.db")
    fleet = launch_fleet(n_coordinators=n_coordinators, sf=sf,
                         workers=1, sqlite_path=sqlite_path,
                         heartbeat_s=0.5)
    urls = fleet.urls
    _FAMS = ("plan_cache_", "plan_template_cache_", "result_cache_",
             "scan_shared_attach_total", "mesh_path_selected_total",
             "serving_requests_total", "fleet_bump_", "fleet_heartbeat_",
             "coordinator_lost_total")
    try:
        # one pinned client per coordinator: warmup and the coherence
        # probe need COORDINATOR-ADDRESSED statements (caches are
        # per-process; FleetClient would smear them across the fleet)
        pinned = [StatementClient(u, user="bench") for u in urls]
        probe = _SERVING_STATEMENTS[0].format(q=10)

        t0 = time.perf_counter()
        cold_rows = pinned[0].execute(probe).rows
        cold_s = time.perf_counter() - t0

        # prepared statements are per-coordinator server state
        for cl in pinned:
            for name, sql in _SERVING_PREPARES:
                cl.execute(f"prepare {name} from {sql}")

        def live_idx():
            return [i for i, c in enumerate(fleet.coordinators)
                    if c["proc"].poll() is None]

        def fleet_snap():
            """(per-coordinator, aggregate) counter snapshots scraped
            from every live coordinator's /v1/metrics."""
            per, agg = {}, {}
            for i in live_idx():
                m = {k: v for k, v in fleet.metrics(i).items()
                     if k.startswith(_FAMS)}
                per[fleet.coordinators[i]["node_id"]] = m
                for k, v in m.items():
                    agg[k] = agg.get(k, 0.0) + v
            return per, agg

        def flush_slo():
            # GET /v1/slo samples the child's store first — the fleet
            # form of the phase-edge flush (degenerate-slo-block fix)
            for i in live_idx():
                fleet.slo(i)

        def run_fleet_phase(statements, kill_at: int = -1):
            """One concurrent phase through FleetClients. With
            ``kill_at >= 0``: SIGKILL that coordinator once a third of
            the statements completed (the chaos drill — still expects
            ZERO failed statements)."""
            for s in sorted(set(statements)):   # per-coordinator warm
                for cl in pinned:
                    if kill_at < 0 or cl is not pinned[kill_at] \
                            or fleet.coordinators[kill_at]["proc"]\
                            .poll() is None:
                        cl.execute(s)
            flush_slo()
            before_per, before = fleet_snap()
            latencies, errors = [], []
            by_group = {"dash": [], "adhoc": []}
            lat_lock = threading.Lock()
            failovers = [0]
            retries = [0]
            n = len(statements)

            def client_loop(ci: int) -> None:
                group = "dash" if ci % 2 == 0 else "adhoc"
                fc = FleetClient(urls, user=f"{group}-{ci}")
                try:
                    for qi in range(per_client):
                        sql = statements[(ci * per_client + qi) % n]
                        t = time.perf_counter()
                        fc.execute(sql)
                        dt = time.perf_counter() - t
                        with lat_lock:
                            latencies.append(dt)
                            by_group[group].append(dt)
                except Exception as e:   # surfaced, not lost
                    errors.append(f"client {ci}: {e}")
                finally:
                    with lat_lock:
                        failovers[0] += fc.failovers_total
                        retries[0] += fc.retries_total
                    fc.close()

            killer = None
            if kill_at >= 0:
                def kill_when_hot():
                    deadline = time.monotonic() + 120
                    while time.monotonic() < deadline:
                        with lat_lock:
                            done = len(latencies)
                        if done >= max(1, n // 3):
                            break
                        time.sleep(0.01)
                    fleet.kill_coordinator(kill_at)
                killer = threading.Thread(target=kill_when_hot)
                killer.start()

            threads = [threading.Thread(target=client_loop, args=(i,))
                       for i in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_s = time.perf_counter() - t0
            if killer is not None:
                killer.join()
            flush_slo()
            after_per, after = fleet_snap()
            delta = {k: after.get(k, 0.0) - before.get(k, 0.0)
                     for k in after}
            per_delta = {
                node: {k: m.get(k, 0.0) - before_per.get(node, {})
                       .get(k, 0.0) for k in m}
                for node, m in after_per.items()}
            latencies.sort()
            for v in by_group.values():
                v.sort()
            return {"lat": latencies, "groups": by_group,
                    "wall_s": wall_s, "delta": delta,
                    "per_delta": per_delta, "errors": errors,
                    "failovers": failovers[0], "retries": retries[0]}

        n = clients * per_client
        known = ("mixed", "execute", "repeated")
        bad = [m for m in mixes if m not in known]
        if bad or not mixes:
            raise ValueError(
                f"SERVING_MIX: unknown phase(s) {bad or mixes} — "
                f"choose from {', '.join(known)}")
        phases = {}
        if "mixed" in mixes:
            phases["mixed"] = run_fleet_phase(_serving_mix(n))
        if "execute" in mixes:
            phases["execute"] = run_fleet_phase(_execute_fleet_mix(n))
        if "repeated" in mixes:
            phases["repeated"] = run_fleet_phase(_repeated_mix(n))
        for name, ph in phases.items():
            assert not ph["errors"], (name, ph["errors"])

        t0 = time.perf_counter()
        warm_rows = pinned[0].execute(probe).rows
        warm_s = time.perf_counter() - t0
        assert warm_rows == cold_rows, "warm re-run changed results"

        # -- coherence probe: write through coordinator 0, observe the
        # bump fold AND the invalidated re-read on coordinator 1 ------
        coh_sql = "select count(*), sum(x) from fleetdb.default.coh"
        pinned[0].execute(
            "create table fleetdb.default.coh as select 1 as x")
        time.sleep(0.2)   # CTAS bump reaches peers before the warm read
        rows_before = pinned[1].execute(coh_sql).rows
        m1 = fleet.metrics(1)
        hits0 = m1.get("result_cache_hit_total", 0.0)
        folds0 = m1.get("fleet_bump_fold_total", 0.0)
        # second identical read on coordinator 1 = its OWN result-cache
        # hit (the cross-coordinator warm entry the write must kill)
        assert pinned[1].execute(coh_sql).rows == rows_before
        xcoord_hits = fleet.metrics(1).get(
            "result_cache_hit_total", 0.0) - hits0
        pinned[0].execute(
            "insert into fleetdb.default.coh select 2 as x")
        deadline = time.monotonic() + 10
        folds_after = folds0
        while time.monotonic() < deadline:
            folds_after = fleet.metrics(1).get(
                "fleet_bump_fold_total", 0.0)
            if folds_after > folds0:
                break
            time.sleep(0.05)
        rows_after = pinned[1].execute(coh_sql).rows
        coherence = {
            "bump_fold_delta": folds_after - folds0,
            "remote_invalidation_observed": folds_after > folds0,
            "xcoord_result_cache_hits": int(xcoord_hits),
            "rows_before": [[int(a), int(b)] for a, b in rows_before],
            "rows_after": [[int(a), int(b)] for a, b in rows_after],
            "row_exact": [[int(a), int(b)] for a, b in rows_after]
            == [[2, 3]],
        }
        assert coherence["remote_invalidation_observed"], coherence
        assert coherence["row_exact"], coherence

        # merged multi-coordinator slo block (all coordinators alive)
        slo_merged = {"coordinators": len(urls),
                      "sample_interval_s": None,
                      "objectives": [], "alerts": [], "timeline": []}
        for i in live_idx():
            node = fleet.coordinators[i]["node_id"]
            blk = fleet.slo(i)
            if slo_merged["sample_interval_s"] is None:
                slo_merged["sample_interval_s"] = \
                    blk.get("sample_interval_s")
            for key in ("objectives", "alerts", "timeline"):
                for row in blk.get(key) or ():
                    slo_merged[key].append(
                        {**row, "coordinator": node})

        # -- the kill drill: lose coordinator N-1 mid-phase -----------
        kill_at = len(urls) - 1
        killed_id = fleet.coordinators[kill_at]["node_id"]
        kp = run_fleet_phase(_serving_mix(n), kill_at=kill_at)
        lost = 0.0
        survivor_lost = []
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            _, agg_now = fleet_snap()
            lost = agg_now.get("coordinator_lost_total", 0.0)
            # wait for the SURVEYED survivor's own sweep, not just any
            # survivor's counter — each coordinator declares the loss
            # on its own heartbeat cadence
            survivor_lost = fleet.fleet_status(0).get("lost", [])
            if lost >= 1.0 and killed_id in survivor_lost:
                break
            time.sleep(0.1)
        kill_block = {
            "killed": killed_id,
            "queries": len(kp["lat"]),
            "failed_queries": len(kp["errors"]),
            "client_failovers": kp["failovers"],
            "client_retries": kp["retries"],
            "coordinator_lost_total": lost,
            "survivor_lost_view": survivor_lost,
        }
        assert kill_block["failed_queries"] == 0, kp["errors"]
        assert lost >= 1.0, kill_block
        assert killed_id in survivor_lost, kill_block

        def rate(d, fam, extra_miss=0.0):
            hits = d.get(f"{fam}_hit_total", 0.0)
            misses = d.get(f"{fam}_miss_total", 0.0) + extra_miss
            return hits / max(hits + misses, 1.0)

        head = phases.get("mixed", next(iter(phases.values())))
        lat, groups = head["lat"], head["groups"]
        wall_s, delta = head["wall_s"], head["delta"]
        qps = round(len(lat) / wall_s, 2)

        def coord_requests(per_delta):
            return {node: sum(v for k, v in d.items()
                              if k.startswith("serving_requests_total"))
                    for node, d in per_delta.items()}

        head_reqs = coord_requests(head["per_delta"])
        per_coordinator_qps = {
            node: round(reqs / wall_s, 2)
            for node, reqs in sorted(head_reqs.items())}

        summary = {
            "metric": f"serving_tpch_sf{sf:g}_qps",
            "value": qps,
            "unit": "queries/s",
            "clients": clients,
            "queries": len(lat),
            "p50_ms": round(_pct(lat, 0.50) * 1e3, 2),
            "p95_ms": round(_pct(lat, 0.95) * 1e3, 2),
            "p99_ms": round(_pct(lat, 0.99) * 1e3, 2),
            "groups": {
                g: {"queries": len(v),
                    "p50_ms": round(_pct(v, 0.50) * 1e3, 2),
                    "p95_ms": round(_pct(v, 0.95) * 1e3, 2),
                    "p99_ms": round(_pct(v, 0.99) * 1e3, 2)}
                for g, v in groups.items()},
            "plan_cache_hit_rate": round(rate(delta, "plan_cache"), 4),
            "result_cache_hit_rate": round(
                rate(delta, "result_cache"), 4),
            "shared_scan_attaches": int(
                delta.get("scan_shared_attach_total", 0.0)),
            "mesh_path_selected": int(
                delta.get("mesh_path_selected_total", 0.0)),
            "cold_ms": round(cold_s * 1e3, 2),
            "warm_ms": round(warm_s * 1e3, 2),
            "warm_speedup": round(cold_s / warm_s, 2),
            "fleet": {
                "coordinators": len(urls),
                "workers": len(fleet.workers),
                "per_coordinator_qps": per_coordinator_qps,
                "aggregate_qps": qps,
                "client_failovers": head["failovers"],
                "coherence": coherence,
                "kill": kill_block,
            },
            "sub_metrics": [
                {"metric": f"serving_tpch_sf{sf:g}_p95_latency_ms",
                 "value": round(_pct(lat, 0.95) * 1e3, 2), "unit": "ms"},
                {"metric": f"serving_tpch_sf{sf:g}_warm_speedup",
                 "value": round(cold_s / warm_s, 2), "unit": "x"},
                {"metric": f"serving_tpch_sf{sf:g}_dash_p99_ms",
                 "value": round(_pct(groups["dash"], 0.99) * 1e3, 2),
                 "unit": "ms"},
                {"metric": f"serving_tpch_sf{sf:g}_adhoc_p99_ms",
                 "value": round(_pct(groups["adhoc"], 0.99) * 1e3, 2),
                 "unit": "ms"},
            ],
        }
        if "execute" in phases:
            ep = phases["execute"]
            edelta = ep["delta"]
            tpl_hits = edelta.get("plan_template_cache_hit_total", 0.0)
            tpl_miss = edelta.get("plan_template_cache_miss_total", 0.0)
            tpl_fb = edelta.get(
                "plan_template_cache_guard_fallback_total", 0.0)
            tpl_rate = (tpl_hits - tpl_fb) / max(tpl_hits + tpl_miss,
                                                 1.0)
            summary["sub_metrics"] += [
                {"metric": f"serving_tpch_sf{sf:g}_execute_qps",
                 "value": round(len(ep["lat"]) / ep["wall_s"], 2),
                 "unit": "queries/s",
                 "p95_ms": round(_pct(ep["lat"], 0.95) * 1e3, 2),
                 "p99_ms": round(_pct(ep["lat"], 0.99) * 1e3, 2)},
                {"metric": f"serving_tpch_sf{sf:g}_template_hit_rate",
                 "value": round(tpl_rate, 4), "unit": "ratio",
                 "guard_fallbacks": int(tpl_fb)},
            ]
        if "repeated" in phases:
            rp = phases["repeated"]
            summary["sub_metrics"] += [
                {"metric": f"serving_tpch_sf{sf:g}_repeated_qps",
                 "value": round(len(rp["lat"]) / rp["wall_s"], 2),
                 "unit": "queries/s",
                 "p95_ms": round(_pct(rp["lat"], 0.95) * 1e3, 2),
                 "p99_ms": round(_pct(rp["lat"], 0.99) * 1e3, 2)},
                {"metric": f"serving_tpch_sf{sf:g}_result_hit_rate",
                 "value": round(rate(rp["delta"], "result_cache"), 4),
                 "unit": "ratio",
                 "partials": int(rp["delta"].get(
                     "result_cache_partial_total", 0.0))},
            ]
        summary["slo"] = slo_merged
        return summary
    finally:
        fleet.stop()


def main_serving() -> None:
    """The serving bench. With ``SERVING_COORDINATORS`` >= 2 it starts N
    coordinator and worker PROCESSES (tools/fleet.py) that each import
    JAX on this host: a chip belongs to one process, so this parent
    must not have initialised a JAX backend and N device-holding
    children need N chips — or ``JAX_PLATFORMS=cpu`` from the caller,
    which is the only form run so far (checked before anything spawns,
    tools/fleet.check_children_can_hold_devices)."""
    import sys
    _enable_compile_cache()
    sf = float(os.environ.get("BENCH_SERVING_SF", "0.01"))
    # SERVING_COORDINATORS >= 2 switches to the horizontal fleet
    # topology (config.py ENV_VARS): N coordinator subprocesses over
    # one shared worker pool, FleetClient round-robin on the client
    # side. Unset/0/1 keeps the classic single-coordinator bench.
    n_coords = int(os.environ.get("SERVING_COORDINATORS", "0"))
    # SERVING_CLIENTS/SERVING_QUERIES are the documented knobs;
    # BENCH_SERVING_* kept for back-compat with r01 runbooks. The
    # fleet default offers LESS client concurrency (same total
    # statement count): the coordinators are subprocesses sharing the
    # host with the load generator, and on a small box 100 client OS
    # threads measure the client-side scheduler, not the fleet — the
    # closed-loop throughput knee sits at a few dozen in-flight
    # statements either way.
    clients = int(os.environ.get(
        "SERVING_CLIENTS", os.environ.get(
            "BENCH_SERVING_CLIENTS",
            "24" if n_coords >= 2 else "100")))
    per_client = int(os.environ.get(
        "SERVING_QUERIES", os.environ.get(
            "BENCH_SERVING_QUERIES",
            "34" if n_coords >= 2 else "8")))
    mixes = tuple(m.strip() for m in os.environ.get(
        "SERVING_MIX", "mixed,execute,repeated").split(",")
        if m.strip())
    if n_coords >= 2:
        from tools.fleet import check_children_can_hold_devices
        check_children_can_hold_devices(n_coords + 1)
        summary = bench_serving_fleet(sf, clients, per_client,
                                      mixes=mixes,
                                      n_coordinators=n_coords)
    else:
        summary = bench_serving(sf, clients, per_client, mixes=mixes)
    line = json.dumps(summary)
    print(line, flush=True)
    out_path = os.environ.get("SERVING_OUT")
    if out_path:
        try:
            tmp = out_path + ".tmp"
            with open(tmp, "w") as f:
                f.write(line + "\n")
            os.replace(tmp, out_path)
        except OSError as e:
            print(f"[bench] SERVING_OUT write failed: {e}",
                  file=sys.stderr)


# ---------------------------------------------------------------------------
# MULTICHIP: the mesh-scaling axis on REAL queries (ROADMAP item 1).
# Every earlier round pinned only a dry-run exit code; this runs
# q1sql/q3/q27/q55 through the engine SQL path at n_devices in
# {1, 2, 4, 8} — n=1 is the single-device executor (the honest
# baseline), n>1 the SPMD mesh path (mesh_execution/mesh_devices) —
# and reports per-query rows/s plus scaling efficiency
# rows_per_sec(n) / (n * rows_per_sec(1)). Results are row-checked
# across device counts, and the mesh selection metric is asserted so a
# silently-local "mesh" number can never pin. BENCH_MULTICHIP_FORCE_CPU=1
# (the default) self-provisions the virtual CPU device platform, which
# says whether the mesh path is right and nothing about a chip; 0
# inherits the devices JAX finds. Either way the summary names the
# platform and device kind it ran on. MULTICHIP_OUT=path writes the
# summary tools/check_bench_regression.py gates with
# ``--kind multichip``; the legacy dry-run ``ok``/``rc`` booleans ride
# on the headline for back-compat.
# ---------------------------------------------------------------------------

#: TPC-H Q3 through the engine SQL path (the BENCH q3 config is a hand
#: pipeline with no SQL text; the mesh axis runs real queries only)
_TPCH_Q3_SQL = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

#: (name, catalog, module attr of the SQL, scanned tables for the
#: rows/s numerator)
_MULTICHIP_QUERIES = (
    ("q1sql", "tpch", "_TPCH_Q1", ("lineitem",)),
    ("q3", "tpch", "_TPCH_Q3_SQL", ("lineitem", "orders", "customer")),
    ("q27", "tpcds", "_DS_Q27",
     ("store_sales", "customer_demographics", "date_dim", "store",
      "item")),
    ("q55", "tpcds", "_DS_Q55", ("store_sales", "date_dim", "item")),
)


def _multichip_rows(rows):
    out = []
    for r in rows:
        out.append(tuple(v.item() if hasattr(v, "item") else v
                         for v in r))
    return out


def _multichip_rows_match(a, b, rel: float = 1e-6) -> bool:
    """Row equality with relative float tolerance: shard-count-
    dependent reduction order legitimately shifts big float64 sums in
    the last ulps, so exact equality would fail spuriously exactly
    when the mesh works (same contract as the parity tests)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                if abs(va - vb) > rel * max(abs(va), abs(vb), 1.0):
                    return False
            elif va != vb:
                return False
    return True


def main_multichip() -> None:
    import sys

    n_max = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8"))
    if os.environ.get("BENCH_MULTICHIP_FORCE_CPU", "1") == "1" \
            and n_max > 1:
        # container default: no TPU — self-provision the virtual CPU
        # platform BEFORE any backend initializes (same contract as
        # the dry run / tests/conftest.py; importing engine modules
        # would initialize the backend, so this is pure env + config).
        # BENCH_MULTICHIP_FORCE_CPU=0 inherits the devices JAX finds.
        xla_flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xla_flags:
            os.environ["XLA_FLAGS"] = (
                xla_flags
                + f" --xla_force_host_platform_device_count={n_max}"
            ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
    _enable_compile_cache()
    import jax

    from presto_tpu.connectors.spi import TableHandle
    from presto_tpu.obs.metrics import REGISTRY

    have = len(jax.devices())
    counts = [n for n in (1, 2, 4, 8) if n <= min(n_max, have)]
    sf = float(os.environ.get("BENCH_MULTICHIP_SF", "0.05"))
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1380"))
    t_start = time.perf_counter()
    results = []

    def emit():
        if not results:
            return
        headline = dict(results[0])
        headline["sub_metrics"] = results[1:]
        # dry-run back-compat keys (MULTICHIP_r01..r05 pinned only
        # these): consumers of the old schema keep reading True
        headline.update({"ok": True, "rc": 0, "skipped": False,
                         "n_devices": max(counts), "sf": sf,
                         "platform": jax.devices()[0].platform,
                         "device_kind": jax.devices()[0].device_kind})
        line = json.dumps(headline)
        print(line, flush=True)
        out_path = os.environ.get("MULTICHIP_OUT")
        if out_path:
            try:
                tmp = out_path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(line + "\n")
                os.replace(tmp, out_path)
            except OSError as e:
                print(f"[bench] MULTICHIP_OUT write failed: {e}",
                      file=sys.stderr)

    def selected() -> float:
        return REGISTRY.value("mesh_path_selected_total")

    for name, catalog, attr, tables in _MULTICHIP_QUERIES:
        elapsed = time.perf_counter() - t_start
        if results and elapsed > budget_s:
            print(f"[bench] budget exhausted ({elapsed:.0f}s); "
                  f"skipping {name}", file=sys.stderr)
            continue
        sql = globals()[attr]
        runner = _shared_runner(catalog, sf)
        conn = _SHARED_CONNS[(catalog, sf)]
        total_rows = sum(
            int(conn.metadata.table_stats(
                TableHandle(catalog, "default", t)).row_count)
            for t in tables)
        base_rps = None
        reference = None
        for n in counts:
            elapsed = time.perf_counter() - t_start
            if results and elapsed > budget_s:
                print(f"[bench] budget exhausted ({elapsed:.0f}s); "
                      f"skipping {name} n={n}", file=sys.stderr)
                break
            props = ({"mesh_execution": "off"} if n == 1 else
                     {"mesh_execution": "auto", "mesh_devices": n})
            print(f"[bench] multichip {name} sf={sf:g} n={n} "
                  f"at {time.perf_counter() - t_start:.0f}s",
                  file=sys.stderr, flush=True)
            sel0 = selected()
            disp0 = REGISTRY.value("mesh_dispatches_total")
            got, secs = _time(
                lambda: runner.execute(sql, properties=props).rows)
            dispatches = REGISTRY.value("mesh_dispatches_total") - disp0
            if n > 1:
                assert selected() > sel0, \
                    f"{name} n={n}: mesh path was not selected"
            rows = _multichip_rows(got)
            if reference is None:
                reference = rows
            else:
                assert _multichip_rows_match(rows, reference), \
                    f"{name} n={n}: rows diverged from n=1"
            rps = total_rows / secs
            metric = (f"multichip_{catalog}_sf{sf:g}_{name}"
                      f"_n{n}_rows_per_sec")
            rec = {"metric": metric, "value": round(rps),
                   "unit": "rows/s", "devices": n,
                   "wall_s": round(secs, 4)}
            if n > 1:
                # host dispatches the timed run cost: the fused
                # exchange's ">= 3x fewer dispatches" evidence rides
                # the pin next to the wall-clock it bought
                rec["dispatches"] = int(dispatches)
                # flight-recorder attribution for the timed run
                # (obs/flight.py): the pin carries WHERE the wall went
                # — tools/mesh_report.py diffs pins bucket-by-bucket
                # and check_bench_regression enforces bucket budgets,
                # so a re-pin must prove overhead moved, not just
                # rows/s
                from presto_tpu.obs.flight import FLIGHTS
                fl = FLIGHTS.last()
                if fl is not None and fl.attribution is not None:
                    rec["attribution"] = fl.attribution
            results.append(rec)
            if n == 1:
                base_rps = rps
            elif base_rps:
                results.append({
                    "metric": (f"multichip_{catalog}_sf{sf:g}_{name}"
                               f"_n{n}_scaling_eff"),
                    "value": round(rps / (n * base_rps), 4),
                    "unit": "x", "devices": n})
            emit()


def main() -> None:
    import sys

    _enable_compile_cache()
    # SF10 default: at SF1 fixed per-query costs (dispatch, result
    # readback) outweigh the device's compute and the ratio measures
    # latency, not throughput (the split is not measured on the v5e)
    sf_q6 = float(os.environ.get("BENCH_SF_Q6",
                                 os.environ.get("BENCH_SF", "10")))
    sf_q1 = float(os.environ.get("BENCH_SF_Q1", "10"))
    sf_q1sql = float(os.environ.get("BENCH_SF_Q1SQL", "10"))
    sf_q3 = float(os.environ.get("BENCH_SF_Q3", "10"))
    # SF10 default for the TPC-DS macro configs (BASELINE config 4 names
    # SF100): at SF1 per-operator dispatch and result readback
    # outweigh the device's compute and the ratio measures latency,
    # not throughput (not measured on the v5e)
    sf_ds = float(os.environ.get("BENCH_SF_DS", "10"))
    # hard wall-clock budget: the driver kills the bench process at
    # ~1800s, so leave headroom — skip remaining configs rather than risk
    # the whole run (and every completed number) being killed
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1380"))
    t_start = time.perf_counter()

    import signal

    class _ConfigTimeout(Exception):
        pass

    def _on_alarm(signum, frame):
        raise _ConfigTimeout()

    alarm_ok = hasattr(signal, "SIGALRM")
    if alarm_ok:
        signal.signal(signal.SIGALRM, _on_alarm)

    def emit(results):
        """Print the CURRENT summary as one JSON line. Called after every
        config (not just at the end) so that if the driver kills this
        process mid-run, the last stdout line is still a complete summary
        of every config that finished — round 4 lost ALL its numbers by
        printing only at exit (BENCH_r04: rc=124, parsed=null).
        BENCH_OUT=path additionally overwrites that file with the same
        summary — the input tools/check_bench_regression.py diffs
        against the latest committed BENCH_r*.json."""
        headline = dict(next((r for r in results if "_q1_" in r["metric"]),
                             results[0]))
        headline["sub_metrics"] = [r for r in results
                                   if r["metric"] != headline["metric"]]
        line = json.dumps(headline)
        print(line, flush=True)
        out_path = os.environ.get("BENCH_OUT")
        if out_path:
            try:
                # write-then-rename: a driver SIGKILL mid-write must not
                # leave a truncated summary (the whole point of emitting
                # per config is surviving exactly that kill)
                tmp = out_path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(line + "\n")
                os.replace(tmp, out_path)
            except OSError as e:
                print(f"[bench] BENCH_OUT write failed: {e}",
                      file=sys.stderr)

    results = []
    global _PROXY_RUNS
    configs = [
        ("q6", sf_q6, bench_q6, "tpch"),
        ("q1", sf_q1, bench_q1, "tpch"),
        ("q1sql", sf_q1sql, bench_q1sql, "tpch"),
        ("q3", sf_q3, bench_q3, "tpch"),
        ("q55", sf_ds, bench_q55, "tpcds"),
        ("q27", sf_ds, bench_q27, "tpcds"),
    ]
    if os.environ.get("BENCH_ORC"):
        # BASELINE config 5 (ORC device decode): slow-tier guarded —
        # writing the ORC dataset costs minutes at interesting SFs
        sf_orc = float(os.environ.get("BENCH_SF_ORC", "1"))
        configs.append(("q6orc", sf_orc, bench_q6orc, "orc"))
    for name, sf, fn, prefix in configs:
        elapsed = time.perf_counter() - t_start
        if results and elapsed > budget_s:
            print(f"[bench] budget exhausted ({elapsed:.0f}s); "
                  f"skipping {name}", file=sys.stderr)
            continue
        print(f"[bench] {name} sf={sf:g} starting at {elapsed:.0f}s",
              file=sys.stderr, flush=True)
        metric = f"{prefix}_sf{sf:g}_{name}_rows_per_sec"
        # pinned proxy: one measured run suffices (results still verify);
        # unpinned — or re-pinning — runs best-of-3 to reject
        # host-contention spikes before the value is frozen
        _PROXY_RUNS = (1 if metric in _load_proxy_pins()
                       and not os.environ.get("BENCH_REPIN") else 3)
        # per-config watchdog: one pathological compile/run must not eat
        # every later config's slot NOR push the whole process past the
        # driver's kill timeout (completed numbers stay reportable)
        if alarm_ok:
            signal.alarm(int(max(budget_s * 1.05 - elapsed, 120)))
        try:
            out = fn(sf)
            total, dev_s, np_s = out[:3]
            extra = out[3] if len(out) > 3 else {}
        except _ConfigTimeout:
            print(f"[bench] {name} exceeded its time slot; skipping",
                  file=sys.stderr, flush=True)
            continue
        finally:
            if alarm_ok:
                signal.alarm(0)
        pinned_s = _pin_proxy_seconds(metric, np_s)
        print(f"[bench] {name} done: {round(total / dev_s):,} rows/s "
              f"(vs {pinned_s / dev_s:.2f}, measured proxy {np_s:.2f}s, "
              f"pinned {pinned_s:.2f}s)", file=sys.stderr, flush=True)
        results.append({
            "metric": metric,
            "value": round(total / dev_s),
            "unit": "rows/s",
            "vs_baseline": round(pinned_s / dev_s, 3),
            "proxy_s_pinned": round(pinned_s, 4),
            "proxy_s_measured": round(np_s, 4),
            **extra,
        })
        emit(results)


if __name__ == "__main__":
    import sys as _sys
    if "serving" in _sys.argv[1:] or os.environ.get("BENCH_SERVING"):
        main_serving()
    elif "multichip" in _sys.argv[1:] \
            or os.environ.get("BENCH_MULTICHIP"):
        main_multichip()
    else:
        main()
