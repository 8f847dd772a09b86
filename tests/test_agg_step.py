"""The aggregation sink (exec/fused.py agg_step): a grouped aggregation
over a small dense key domain folds its input as ONE program a scan
batch — the filter/project chain under it, the partial group-by and the
merge into the running state in one launch.

The sink is a pure performance decision, so every case holds its answer
to the per-operator path's, row for row (the same kernels; only the
order in which partial sums meet differs). The other tests pin what
bought the time: launches a query, and the eager merge
(``concat_batches``, ``remap_codes``) not growing with the batches."""
import pytest

from presto_tpu import batch as batch_mod
from presto_tpu import types as T
from presto_tpu.batch import Batch
from presto_tpu.errors import QueryError
from presto_tpu.exec import local as local_exec
from presto_tpu.exec import spill as spill_mod
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY

COUNTERS = ("agg_step_selected_total", "agg_step_declined_total",
            "agg_step_batches_total", "agg_step_flushes_total",
            "jit_cache_invocations_total",
            "expr_program_invocations_total")

Q1_SHAPE = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_discount) as avg_disc, min(l_shipdate) as first_ship,
  count(*) as count_order
from lineitem
where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""


def _read() -> dict:
    return {n: REGISTRY.counter(n).value for n in COUNTERS}


def _delta(before: dict) -> dict:
    return {n: v - before[n] for n, v in _read().items()}


def _rows_equal(got, want):
    assert len(got) == len(want), (got, want)
    for rg, rw in zip(got, want):
        for x, y in zip(rg, rw):
            if isinstance(x, float) and isinstance(y, float):
                assert abs(x - y) <= 1e-12 * max(1.0, abs(y)), (rg, rw)
            else:
                assert x == y, (rg, rw)


def _canon(rows):
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


@pytest.fixture()
def per_operator(monkeypatch):
    """Context-free switch: calling it pins every later query of the
    test to the per-operator path (the sink's chain check says no)."""
    def pin():
        monkeypatch.setattr(local_exec._Executor, "_agg_step_chain",
                            lambda self, node, aggs: None)
    return pin


@pytest.fixture(scope="module")
def tpch():
    # 1200 rows a batch over SF0.002's ~12k lineitems: ten-odd batches
    return LocalRunner(tpch_sf=0.002, rows_per_batch=1200)


@pytest.fixture()
def mem():
    """A runner whose memory catalog holds table ``t`` (flag varchar,
    ok boolean, v bigint, x double), one scan batch an append."""
    r = LocalRunner(tpch_sf=0.002)
    conn = r.session.catalogs.get("memory")

    def table(name, *batches):
        schema = None
        for cols in batches:
            b = Batch.from_pydict(cols)
            if schema is None:
                schema = b.schema
                conn.create_table(name, schema)
            conn.append(name, b)
        return r
    r.table = table
    return r


def _flags(flag, ok, v, x):
    return {"flag": (T.VARCHAR, flag), "ok": (T.BOOLEAN, ok),
            "v": (T.BIGINT, v), "x": (T.DOUBLE, x)}


BATCH_A = _flags(["a", "b", "a", None, "b", "a"],
                 [True, False, None, True, True, False],
                 [1, 2, 3, 4, 5, 6], [0.5, 1.5, 2.5, 3.5, 4.5, 5.5])
BATCH_B = _flags(["a", "b", None, "b"], [False, False, True, None],
                 [7, 8, 9, 10], [6.5, 7.5, 8.5, 9.5])
#: another vocabulary, in another order: the state's layout changes
BATCH_C = _flags(["c", "b", "c", "a"], [True, True, False, None],
                 [11, 12, 13, 14], [10.5, 11.5, 12.5, 13.5])

AGGS = "sum(v) s, count(x) c, avg(x) a, min(v) mn, max(x) mx, count(*) n"

#: (case, batches, sql, selected, flushes)
MEMORY_CASES = [
    ("boolean_key", (BATCH_A, BATCH_B),
     f"select ok, {AGGS} from memory.default.t group by ok", 1, 0),
    ("null_keys", (BATCH_A, BATCH_B),
     f"select flag, ok, {AGGS} from memory.default.t where v <> 5 "
     f"group by flag, ok", 1, 0),
    ("filter_keeps_nothing", (BATCH_A, BATCH_B),
     f"select flag, {AGGS} from memory.default.t where v > 100 "
     f"group by flag", 1, 0),
    ("projected_key_and_argument", (BATCH_A, BATCH_B),
     "select upper(flag) f, sum(x * (1 + v)) s, count(*) n "
     "from memory.default.t where x < 9 group by upper(flag)", 1, 0),
    ("masked_aggregate_over_mark_distinct", (BATCH_A,),
     "select flag, count(distinct ok) n, sum(v) s "
     "from memory.default.t group by flag", 1, 0),
    ("no_chain_under_the_aggregation", (BATCH_A, BATCH_B),
     "select flag, sum(v) s from memory.default.t group by flag", 1, 0),
    ("changed_dictionary_flushes", (BATCH_A, BATCH_C, BATCH_B),
     f"select flag, ok, {AGGS} from memory.default.t where v <> 5 "
     f"group by flag, ok", 1, 2),
]


@pytest.mark.parametrize(
    "batches,sql,selected,flushes",
    [c[1:] for c in MEMORY_CASES], ids=[c[0] for c in MEMORY_CASES])
def test_step_path_equals_per_operator_path(mem, per_operator, batches,
                                            sql, selected, flushes):
    r = mem.table("t", *batches)
    before = _read()
    got = r.execute(sql).rows
    d = _delta(before)
    assert d["agg_step_selected_total"] == selected
    assert d["agg_step_declined_total"] == 0
    assert d["agg_step_batches_total"] == len(batches)
    assert d["agg_step_flushes_total"] == flushes
    per_operator()
    before = _read()
    want = r.execute(sql).rows
    assert _delta(before)["agg_step_batches_total"] == 0
    _rows_equal(_canon(got), _canon(want))
    assert want or "v > 100" in sql


def test_q1_shape_equals_per_operator_path(tpch, per_operator):
    before = _read()
    got = tpch.execute(Q1_SHAPE).rows
    d = _delta(before)
    assert d["agg_step_selected_total"] == 1
    assert d["agg_step_batches_total"] >= 10
    assert d["agg_step_flushes_total"] == 0
    per_operator()
    want = tpch.execute(Q1_SHAPE).rows
    assert len(want) == 4
    _rows_equal(got, want)


def test_empty_input_yields_no_group(mem):
    r = mem.table("t", BATCH_A)
    conn = r.session.catalogs.get("memory")
    conn.create_table("e", conn.schemas["t"])
    before = _read()
    assert r.execute("select flag, count(*) from memory.default.e "
                     "where v > 0 group by flag").rows == []
    d = _delta(before)
    assert d["agg_step_batches_total"] == 0
    assert d["agg_step_selected_total"] == 0


def _partial_states(runner, sql, monkeypatch):
    """Run ``sql``'s aggregation as a PARTIAL step (what a cluster
    worker's fragment does) and finish it with a FINAL step over the
    states it ships: (partial's batches, final rows)."""
    import dataclasses
    seen = {}
    real = local_exec._Executor._AggregationNode

    def split(self, node):
        if node.step != "single":
            yield from real(self, node)
            return
        states = list(real(self, dataclasses.replace(node,
                                                     step="partial")))
        seen["states"] = states
        key_idx = tuple(range(len(node.group_indices)))
        final = dataclasses.replace(node, step="final",
                                    group_indices=key_idx)
        self._resumed[final.child] = iter(states)
        yield from real(self, final)
    monkeypatch.setattr(local_exec._Executor, "_AggregationNode", split)
    rows = runner.execute(sql).rows
    return seen["states"], rows


def test_partial_step_ships_one_state(mem, monkeypatch):
    r = mem.table("t", BATCH_A, BATCH_B)
    sql = (f"select flag, ok, {AGGS} from memory.default.t "
           f"where v <> 5 group by flag, ok")
    want = r.execute(sql).rows
    before = _read()
    states, got = _partial_states(r, sql, monkeypatch)
    d = _delta(before)
    assert d["agg_step_selected_total"] == 1
    assert d["agg_step_batches_total"] == 2
    assert len(states) == 1
    _rows_equal(_canon(got), _canon(want))


def test_projected_expression_that_raises_reaches_check_errors(
        mem, per_operator):
    r = mem.table("t", BATCH_A, BATCH_B)
    sql = ("select flag, sum(10 / (v - 3)) s from memory.default.t "
           "group by flag")
    before = _read()
    with pytest.raises(QueryError) as step_err:
        r.execute(sql)
    assert _delta(before)["agg_step_batches_total"] == 2
    per_operator()
    with pytest.raises(QueryError) as op_err:
        r.execute(sql)
    assert step_err.value.code == op_err.value.code
    # dead rows raise nothing: the filter takes the zero divisor out
    ok = ("select flag, sum(10 / (v - 3)) s from memory.default.t "
          "where v <> 3 group by flag")
    assert len(r.execute(ok).rows) == 3


def test_has_params_chain_is_declined(mem):
    r = mem.table("t", BATCH_A, BATCH_B)
    props = {"plan_template_cache": True}
    # a DOUBLE literal: no scan bound is derived from it, so no guard
    # sends a later binding back to a plan of its own (without Params)
    sql = ("select flag, sum(v) s, count(*) n from memory.default.t "
           "where x > {} group by flag order by flag")
    want = [r.execute(sql.format(n)).rows for n in (2.25, 4.75)]
    before = _read()
    got = [r.execute(sql.format(n), properties=props).rows
           for n in (2.25, 4.75)]
    d = _delta(before)
    assert got == want
    assert d["agg_step_selected_total"] == 0
    assert d["agg_step_declined_total"] == 2
    assert d["agg_step_batches_total"] == 0


def test_shared_interior_node_is_declined(mem):
    """The same filtered projection under two DIFFERENT aggregations of
    a UNION: the subplan is memoized (mark_shared) and runs standalone,
    so the sink must not trace it a second time inside its step."""
    r = mem.table("t", BATCH_A, BATCH_B)
    one = ("select flag, {}(v) s from memory.default.t where v > 2 "
           "group by flag")
    before = _read()
    rows = r.execute(one.format("sum") + " union all "
                     + one.format("max")).rows
    d = _delta(before)
    assert d["agg_step_batches_total"] == 0
    assert d["agg_step_selected_total"] == 0
    assert d["agg_step_declined_total"] == 2
    before = _read()
    apart = (r.execute(one.format("sum")).rows
             + r.execute(one.format("max")).rows)
    assert _delta(before)["agg_step_selected_total"] == 2
    assert _canon(rows) == _canon(apart)


@pytest.mark.parametrize("case,sql", [
    ("integer_key",
     "select v % 3 k, count(*) n from memory.default.t group by v % 3"),
    ("wide_state_aggregate",
     "select flag, sum(cast(v as decimal(38,2))) d from memory.default.t "
     "group by flag"),
    ("task_concurrency_2",
     "select flag, count(*) n from memory.default.t group by flag"),
    ("dense_grouping_off",
     "select flag, count(*) n from memory.default.t group by flag"),
])
def test_what_else_declines(mem, case, sql):
    r = mem.table("t", BATCH_A, BATCH_B)
    props = {"task_concurrency_2": {"task_concurrency": 2},
             "dense_grouping_off": {"dense_grouping": False}}.get(case)
    before = _read()
    rows = r.execute(sql, properties=props).rows
    d = _delta(before)
    assert rows
    assert d["agg_step_selected_total"] == 0
    assert d["agg_step_declined_total"] == 1
    assert d["agg_step_batches_total"] == 0


def test_domain_past_the_dense_limit_hands_the_source_back(
        mem, per_operator):
    """Keys the chain check cannot refuse (strings) whose domain the
    first batch shows to be too large for the batch: the sink has pulled
    that batch already and hands it and the rest to the per-operator
    path, none lost and none twice."""
    n = 200                       # capacity 256 < (200 + 1) * 3 slots
    def rows(lo):
        return _flags([f"k{lo + i}" for i in range(n)],
                      [i % 2 == 0 for i in range(n)],
                      list(range(lo, lo + n)), [float(i) for i in range(n)])
    r = mem.table("t", rows(0), rows(100))
    sql = ("select flag, ok, sum(v) s, count(*) c from memory.default.t "
           "where v >= 0 group by flag, ok")
    before = _read()
    got = r.execute(sql).rows
    d = _delta(before)
    assert d["agg_step_selected_total"] == 0
    assert d["agg_step_declined_total"] == 1
    assert sum(r_[3] for r_ in got) == 2 * n
    per_operator()
    _rows_equal(_canon(got), _canon(r.execute(sql).rows))


def test_explain_analyze_bills_the_chain_to_the_aggregation(tpch):
    lines = [r[0] for r in tpch.execute(
        "explain analyze " + Q1_SHAPE).rows]
    txt = "\n".join(lines)
    agg = next(ln for ln in lines if ln.lstrip().startswith("- Aggregate"))
    assert "4 rows, 1 batches" in agg and "[device " in agg, txt
    under = lines[lines.index(agg) + 1:]
    # the interior Filter/Project never ran standalone
    assert under[0].lstrip().startswith("- Project") \
        and under[0].endswith("[not executed]"), txt
    assert under[1].lstrip().startswith("- Filter") \
        and under[1].endswith("[not executed]"), txt
    assert under[2].lstrip().startswith("- TableScan") \
        and "batches]" in under[2], txt
    assert any(ln.split()[:1] == ["agg_step"] for ln in lines), txt


def test_q1_shape_launches_one_program_a_batch(tpch):
    tpch.execute(Q1_SHAPE)        # warm: compiles are not launches
    before = _read()
    tpch.execute(Q1_SHAPE)
    d = _delta(before)
    n = d["agg_step_batches_total"]
    assert n >= 10
    launches = (d["jit_cache_invocations_total"]
                + d["expr_program_invocations_total"])
    assert launches <= n + 6, d


def test_eager_merge_does_not_grow_with_the_batches(monkeypatch):
    """``concat_batches`` and ``remap_codes`` are eager jnp code, a
    launch an op: on the sink's path they run a constant number of
    times a query, whatever the number of batches (the per-operator
    path merges every sixteenth partial through them)."""
    calls = {"concat": 0, "remap": 0}
    real_concat, real_remap = batch_mod.concat_batches, batch_mod.remap_codes

    def concat(*a, **k):
        calls["concat"] += 1
        return real_concat(*a, **k)

    def remap(*a, **k):
        calls["remap"] += 1
        return real_remap(*a, **k)
    monkeypatch.setattr(batch_mod, "remap_codes", remap)
    monkeypatch.setattr(batch_mod, "concat_batches", concat)
    monkeypatch.setattr(spill_mod, "concat_batches", concat)
    monkeypatch.setattr(local_exec, "concat_batches", concat)
    seen = []
    for rows_per_batch in (1600, 300):       # 8 and 41 batches
        r = LocalRunner(tpch_sf=0.002, rows_per_batch=rows_per_batch)
        r.execute(Q1_SHAPE)
        calls.update(concat=0, remap=0)
        before = _read()
        r.execute(Q1_SHAPE)
        seen.append((_delta(before)["agg_step_batches_total"],
                     calls["concat"], calls["remap"]))
    (n_few, *few), (n_many, *many) = seen
    assert n_few <= 10 and n_many >= 40, seen
    assert few == many, seen
