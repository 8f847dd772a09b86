"""Sketch-style aggregates: approx_distinct and approx_percentile.

Global approx_distinct carries REAL bounded HLL register state
(ops/sketch.py) through partial -> exchange -> final, like the reference
(reference operator/aggregation/state/HyperLogLogState.java); grouped
approx_distinct keeps the exact mark-distinct lowering (unbounded group
counts would make the dense register tile unbounded; exact is within any
sketch's error bound). Global numeric approx_percentile likewise carries
bounded mergeable log-linear histogram state (ops/sketch.py qd_*,
relative value error <= 1/(2*QD_L); reference
state/DigestAndPercentileState.java); grouped and string forms drain
into an exact segmented-sort select, hash-partitioned by group key.
"""
import numpy as np
import pytest

#: documented bound of the quantile histogram (ops/sketch.py): midpoint
#: of a 1/QD_L-relative-width bin, plus integer-rounding slack
QD_REL = 1.0 / 64 + 1e-9


def within_qd(got, exact):
    if exact == 0:
        return abs(float(got)) <= 1e-12
    return abs(float(got) - float(exact)) <= QD_REL * abs(float(exact)) + 0.5


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.01)


@pytest.fixture(scope="module")
def dist(runner, mesh_runner):
    return mesh_runner(catalogs=runner.session.catalogs,
                       n_devices=8, rows_per_batch=1 << 12)


def _numpy_lineitem(runner, cols):
    rows = runner.execute(
        f"select {', '.join(cols)} from lineitem").rows
    return [np.asarray(c) for c in zip(*rows)]


def nearest_rank(values, p):
    v = np.sort(values)
    if len(v) == 0:
        return None
    k = min(max(int(np.ceil(p * len(v))) - 1, 0), len(v) - 1)
    return v[k]


def test_global_approx_distinct_hll(runner):
    """Global approx_distinct runs the HLL sketch: estimates land within
    a few standard errors of the exact count (deterministic hashing, so
    the outcome is stable run to run)."""
    got = runner.execute(
        "select approx_distinct(l_orderkey), approx_distinct(l_returnflag) "
        "from lineitem").rows[0]
    want = runner.execute(
        "select count(distinct l_orderkey), count(distinct l_returnflag) "
        "from lineitem").rows[0]
    # default standard error 2.3%%: allow 4 sigma on the big count
    assert abs(got[0] - want[0]) <= max(0.1 * want[0], 2), (got, want)
    assert got[1] == want[1]     # 3 distinct values: exact in HLL range


def test_grouped_approx_distinct_stays_exact(runner):
    got = runner.execute(
        "select l_returnflag, approx_distinct(l_suppkey) from lineitem "
        "group by 1 order by 1").rows
    want = runner.execute(
        "select l_returnflag, count(distinct l_suppkey) from lineitem "
        "group by 1 order by 1").rows
    assert got == want


def test_approx_distinct_error_parameter(runner):
    """approx_distinct(x, e): a coarser budget shrinks the register
    vector; estimates stay within a few multiples of e."""
    want = runner.execute(
        "select count(distinct l_orderkey) from lineitem").rows[0][0]
    got = runner.execute(
        "select approx_distinct(l_orderkey, 0.26) from lineitem"
    ).rows[0][0]
    assert abs(got - want) <= 0.6 * want, (got, want)
    import pytest
    with pytest.raises(Exception):
        runner.execute(
            "select approx_distinct(l_orderkey, 0.5) from lineitem")


def test_global_approx_distinct_empty_and_null(runner):
    rows = runner.execute(
        "select approx_distinct(l_orderkey) from lineitem "
        "where l_orderkey < 0").rows
    assert rows == [(0,)]


def test_global_percentile(runner):
    """Global numeric percentiles run the bounded histogram sketch:
    within the documented relative-error bound of exact nearest-rank."""
    (qty,) = _numpy_lineitem(runner, ["l_quantity"])
    got = runner.execute(
        "select approx_percentile(l_quantity, 0.5), "
        "approx_percentile(l_quantity, 0.9), "
        "approx_percentile(l_quantity, 0.0), "
        "approx_percentile(l_quantity, 1.0) from lineitem").rows[0]
    for g, p in zip(got, (0.5, 0.9, 0.0, 1.0)):
        assert within_qd(g, nearest_rank(qty, p)), (p, g)


def test_grouped_percentile(runner):
    rf, price = _numpy_lineitem(runner, ["l_returnflag", "l_extendedprice"])
    got = runner.execute(
        "select l_returnflag, approx_percentile(l_extendedprice, 0.5), "
        "count(*) from lineitem group by 1 order by 1").rows
    assert len(got) == len(set(rf))
    for flag, med, cnt in got:
        sel = price[rf == flag]
        assert cnt == len(sel)
        assert float(med) == float(nearest_rank(sel, 0.5)), flag


def test_percentile_mixed_with_regular_aggs(runner):
    rf, price = _numpy_lineitem(runner, ["l_returnflag", "l_extendedprice"])
    got = runner.execute(
        "select l_returnflag, sum(l_extendedprice), "
        "approx_percentile(l_extendedprice, 0.25), avg(l_extendedprice) "
        "from lineitem group by 1 order by 1").rows
    for flag, s, q25, avg in got:
        sel = price[rf == flag]
        assert abs(float(s) - round(sel.sum(), 2)) < 1e-6 * abs(sel.sum())
        assert float(q25) == float(nearest_rank(sel, 0.25))
        assert abs(float(avg) - sel.mean()) < 1e-6 * abs(sel.mean())


def test_percentile_of_integers(runner):
    got = runner.execute(
        "select approx_percentile(l_linenumber, 0.5) from lineitem").rows
    assert isinstance(got[0][0], (int, np.integer))


def test_percentile_empty_input(runner):
    got = runner.execute(
        "select approx_percentile(l_quantity, 0.5) from lineitem "
        "where l_quantity < -1").rows
    assert got == [(None,)]


def test_percentile_nonconstant_p_rejected(runner):
    from presto_tpu.sql.analyzer import AnalysisError
    with pytest.raises(AnalysisError):
        runner.execute("select approx_percentile(l_quantity, l_discount) "
                       "from lineitem")


def test_percentile_varchar_lexicographic(runner):
    # dictionary codes are appearance-ordered; the kernel must sort by
    # lexicographic rank, not raw code
    names = sorted(r[0] for r in runner.execute(
        "select n_name from nation").rows)
    got = runner.execute(
        "select approx_percentile(n_name, 0.5) from nation").rows[0][0]
    k = max(int(np.ceil(0.5 * len(names))) - 1, 0)
    assert got == names[k]


def test_percentile_multiple_ps_share_input(runner):
    (qty,) = _numpy_lineitem(runner, ["l_quantity"])
    got = runner.execute(
        "select approx_percentile(l_quantity, 0.25), "
        "approx_percentile(l_quantity, 0.5), "
        "approx_percentile(l_quantity, 0.75) from lineitem").rows[0]
    for g, p in zip(got, (0.25, 0.5, 0.75)):
        assert within_qd(g, nearest_rank(qty, p))


def test_split_part_nonpositive_index_errors(runner):
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError):
        runner.execute("select split_part('a:b', ':', 0)")


def test_split_part_out_of_range_is_null(runner):
    assert runner.execute(
        "select split_part('a:b', ':', 5)").rows == [(None,)]


def test_distributed_percentile(runner, dist):
    want = runner.execute(
        "select l_returnflag, approx_percentile(l_extendedprice, 0.5) "
        "from lineitem group by 1 order by 1").rows
    got = dist.execute(
        "select l_returnflag, approx_percentile(l_extendedprice, 0.5) "
        "from lineitem group by 1 order by 1").rows
    assert [(a, float(b)) for a, b in got] \
        == [(a, float(b)) for a, b in want]


@pytest.mark.slow
def test_distributed_global_percentile(runner, dist):
    # global (ungrouped) sketch merge across shards; the grouped
    # distributed path stays tier-1 via test_distributed_percentile —
    # this single-row parity check costs ~45s of compile, slow lane
    want = runner.execute(
        "select approx_percentile(l_quantity, 0.9) from lineitem").rows
    got = dist.execute(
        "select approx_percentile(l_quantity, 0.9) from lineitem").rows
    assert float(got[0][0]) == float(want[0][0])


def test_distributed_approx_distinct(runner, dist):
    """Grouped approx_distinct (exact lowering) must survive the
    distributed exchange: mark-distinct repartitions by (group, value),
    so shards count disjoint value sets."""
    q = ("select l_returnflag, approx_distinct(l_suppkey) "
         "from lineitem group by 1 order by 1")
    assert dist.execute(q).rows == runner.execute(q).rows


def test_distributed_global_approx_distinct(runner, dist):
    """Global approx_distinct ships O(1) HLL register state through the
    mesh exchange (partial on every shard, merged at the single final):
    the distributed estimate must equal the local one bit-for-bit —
    register maxima are associative and hashing is deterministic."""
    q = "select approx_distinct(l_orderkey) from lineitem"
    assert dist.execute(q).rows == runner.execute(q).rows


def test_cluster_global_percentile_with_varchar_aggs():
    """Fragmenter-split global percentile: the FINAL node consumes state
    columns (varchar min/max state + qdigest tile); the executor must
    not re-evaluate the drain decision against that state layout
    (regression: raw-input indices pointing at a varchar state column
    misrouted the final step into the exact drain)."""
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.server.worker import WorkerServer

    workers = [WorkerServer(tpch_sf=0.01) for _ in range(2)]
    for w in workers:
        w.start()
    try:
        runner = ClusterRunner(
            [f"http://127.0.0.1:{w.port}" for w in workers],
            tpch_sf=0.01, heartbeat=False)
        sql = ("select max(l_shipmode), max(l_comment), "
               "approx_percentile(l_quantity, 0.5) from lineitem")
        got = runner.execute(sql).rows[0]
        want = runner.local.execute(sql).rows[0]
        assert got[:2] == want[:2]
        assert float(got[2]) == float(want[2])
    finally:
        for w in workers:
            w.stop()


def test_sketch_percentile_nan_sorts_last():
    """NaN bins into the top slot, matching the exact path's sort-last
    rank behavior (not the zero bin)."""
    import jax.numpy as jnp
    from presto_tpu.ops.sketch import QD_BINS, qd_bin, qd_update

    vals = jnp.asarray([float("nan"), 10.0, 20.0])
    assert int(qd_bin(vals)[0]) == QD_BINS - 1
    counts = qd_update(jnp.ones(3, bool), vals)
    from presto_tpu.ops.sketch import qd_estimate
    # nearest-rank k=ceil(0.5*3)=2 over [10, 20, NaN] -> 20, exactly
    # what the exact path's sort-NaN-last selection returns
    est, ok = qd_estimate(counts, 0.5)
    assert abs(float(est) - 20.0) <= 20.0 / 64 + 1e-9


def test_qdigest_state_is_fixed_size():
    """The percentile partial state is O(1) in input rows: one
    fixed-size histogram tile regardless of input size (the reference's
    bounded-memory contract, state/DigestAndPercentileState.java)."""
    from presto_tpu.batch import Batch
    from presto_tpu import types as T
    from presto_tpu.ops.aggregation import AggSpec, global_aggregate
    from presto_tpu.ops.sketch import QD_BINS
    from presto_tpu.types import QdigestStateType

    for n in (1 << 10, 1 << 14):
        b = Batch.from_pydict({"x": (T.DOUBLE,
                                     [float(i) for i in range(n)])})
        part = global_aggregate(
            b, [AggSpec("approx_percentile", 0, T.DOUBLE, "q", param=0.5)],
            mode="partial")
        (state_col,) = [c for c in part.columns
                        if isinstance(c.type, QdigestStateType)]
        assert state_col.data.shape == (128, QD_BINS)  # independent of n


def test_qdigest_partials_merge_exactly():
    """Chunked partial -> merge -> final equals one single pass: bin
    counts are integers, so merging is associative and exact."""
    from presto_tpu.batch import Batch, concat_batches
    from presto_tpu import types as T
    from presto_tpu.ops.aggregation import AggSpec, global_aggregate

    rng = np.random.default_rng(11)
    data = rng.lognormal(1.0, 1.5, 4096).tolist()
    aggs = [AggSpec("approx_percentile", 0, T.DOUBLE, "q", param=0.9)]
    whole = Batch.from_pydict({"x": (T.DOUBLE, data)})
    one = global_aggregate(global_aggregate(whole, aggs, mode="partial"),
                           aggs, mode="final")
    parts = [global_aggregate(
        Batch.from_pydict({"x": (T.DOUBLE, data[i::4])}), aggs,
        mode="partial") for i in range(4)]
    merged = global_aggregate(concat_batches(parts), aggs, mode="final")
    assert float(one.columns[0].data[0]) == float(merged.columns[0].data[0])
    assert within_qd(float(one.columns[0].data[0]),
                     nearest_rank(np.asarray(data), 0.9))


def test_hll_state_is_fixed_size():
    """The partial state is O(1) in input rows: one register vector per
    group regardless of input size (the reference's bounded-memory
    contract, state/HyperLogLogState.java)."""
    import jax.numpy as jnp
    from presto_tpu.batch import Batch
    from presto_tpu import types as T
    from presto_tpu.ops.aggregation import AggSpec, global_aggregate
    from presto_tpu.types import HllStateType

    for n in (1 << 10, 1 << 14):
        b = Batch.from_pydict({"x": (T.BIGINT, list(range(n)))})
        part = global_aggregate(
            b, [AggSpec("approx_distinct", 0, T.BIGINT, "d")],
            mode="partial")
        (state_col,) = [c for c in part.columns
                        if isinstance(c.type, HllStateType)]
        assert state_col.data.shape == (128, 2048)   # independent of n
