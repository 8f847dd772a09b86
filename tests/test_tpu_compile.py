"""Ahead-of-time compiles of the main path's kernels for a DESCRIBED
TPU v5e (no chip attached): the TPU compiler is installed in the
sandbox and refuses here what it would refuse on the chip, at no chip
time. Sizes are the ones chip_smoke.py drives at TPC-H SF10.

Nothing touches ``jax.experimental.topologies`` at import, in a skipif
or in a parametrize: only one process may load libtpu, so the topology
is described inside a module-scoped fixture (the xdist worker that is
handed this file) and every test of it lives in this one file. A
passing compile is not a chip run and is never reported as one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from presto_tpu.config import SESSION_PROPERTIES
from presto_tpu.ops import pallas_join, pallas_scan

N_ROWS = 1 << 23          # sort-path group-by rows (ops/aggregation.py)
N_BATCH = 1 << 20         # rows_per_batch on the SQL path
N_SLOTS = 1 << 17         # direct-join lookup tables


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip — keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def test_cumsum_i32_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip)
    c = _compile(lambda a: pallas_scan.cumsum_i32(a, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()


def test_segment_sum_sorted_i64_compiles_for_v5e(one_chip):
    v = jax.ShapeDtypeStruct((N_ROWS,), jnp.int64, sharding=one_chip)
    s = jax.ShapeDtypeStruct((N_BATCH,), jnp.int32, sharding=one_chip)
    c = _compile(
        lambda vals, starts: pallas_scan.segment_sum_sorted_i64(
            vals, starts, N_BATCH, interpret=False), v, s)
    assert "tpu_custom_call" in c.as_text()


def test_q6_step_compiles_for_v5e(one_chip):
    """The jitted Q6 scan-filter-project-aggregate step of
    ``__graft_entry__.entry()`` rebuilt at the SQL path's batch size."""
    import __graft_entry__ as graft
    step, (batch,) = graft.entry()

    def widen(leaf):
        assert leaf.shape[0] == batch.capacity, leaf.shape
        return jax.ShapeDtypeStruct((N_BATCH,) + leaf.shape[1:],
                                    leaf.dtype, sharding=one_chip)
    big = jax.tree_util.tree_map(widen, batch)
    c = _compile(step, big)
    assert c.memory_analysis() is not None


def test_q1_agg_step_compiles_for_v5e(one_chip, monkeypatch):
    """The aggregation sink's program (``exec/fused.py`` ``agg_step``:
    TPC-H Q1's filter, projections, partial group-by and merge in one
    launch) at the SQL path's batch size, with the stages, state and
    scan-batch layout a real Q1 gives it."""
    from presto_tpu.exec import fused
    from presto_tpu.exec import local as local_exec
    from presto_tpu.exec.runner import LocalRunner
    seen = {}
    real = local_exec._Executor._agg_step_states

    def capture(self, node, chain, group, aggs, kb):
        seen["key"] = (chain[0], tuple(group), tuple(aggs), kb)
        seen["batch"] = next(iter(self.run(chain[1])))
        return real(self, node, chain, group, aggs, kb)
    monkeypatch.setattr(local_exec._Executor, "_agg_step_states", capture)
    LocalRunner(tpch_sf=0.002).execute("""
        select l_returnflag, l_linestatus, sum(l_quantity),
          sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
          sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
          avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
        from lineitem where l_shipdate <= date '1998-09-02'
        group by l_returnflag, l_linestatus""")
    key, batch = seen["key"], seen["batch"]
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    layout, state = fused.agg_step_start(
        *key, treedef, tuple((x.shape, x.dtype) for x in leaves))
    assert layout.capacity == 128 and len(state) <= 4

    def on_chip(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def widened(leaf):
        return jax.ShapeDtypeStruct((N_BATCH,) + leaf.shape[1:],
                                    leaf.dtype, sharding=one_chip)
    c = fused.agg_step(*key, layout).fn.lower(
        jax.tree_util.tree_map(on_chip, state),
        jax.tree_util.tree_map(widened, batch)).compile()
    assert "jit_op_agg_step" in c.as_text()
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("cap", [1 << 15, N_BATCH])
def test_compact_compiles_for_v5e_without_a_scatter(one_chip, cap):
    """``jit_op_compact`` over TPC-H Q6's filter output at the SQL
    path's batch size (2^20 lanes to the 2^15 the 1.9 % filter leaves,
    and to the batch's own capacity, the cross join's build side): the
    TPU compiler's program holds no scatter, which ``jnp.nonzero``'s did
    (~100 ms a batch on the v5e), and no sort."""
    import re
    import __graft_entry__ as graft
    from presto_tpu.ops.jitcache import _compact
    _, (batch,) = graft.entry()

    def widen(leaf):
        return jax.ShapeDtypeStruct((N_BATCH,) + leaf.shape[1:],
                                    leaf.dtype, sharding=one_chip)
    c = _compact(cap).fn.lower(
        jax.tree_util.tree_map(widen, batch)).compile()
    text = c.as_text()
    assert "jit_op_compact" in text
    assert not re.findall(r"\s(scatter|sort)\(", text)
    assert c.memory_analysis() is not None


def test_state_merge_compiles_for_v5e_without_a_sort_or_a_gather(one_chip):
    """``jit_op_grouped_aggregate_merge`` over TPC-H Q18's subquery
    state (an order key, a DOUBLE sum and its count) at 2^20 lanes a
    side: the merge network, the pair reducers and the compress network
    are elementwise passes, and the branch that appends one state to
    the other where their key ranges are disjoint (ISSUE 38) is a
    conditional around them, chosen on the device; the TPU compiler's
    program holds no sort, no gather and no scatter."""
    import re
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Schema
    from presto_tpu.ops.aggregation import AggSpec, grouped_aggregate
    from presto_tpu.ops.jitcache import _merge_states
    aggs = (AggSpec("sum", 1, T.DOUBLE, "s"),)
    state = grouped_aggregate(Batch.from_arrays(
        Schema([("k", T.BIGINT), ("v", T.DOUBLE)]),
        [[1, 2, 2], [1.0, 2.0, 3.0]], num_rows=3), [0], aggs,
        mode="partial")

    def widen(leaf):
        return jax.ShapeDtypeStruct((N_BATCH,) + leaf.shape[1:],
                                    leaf.dtype, sharding=one_chip)
    side = jax.tree_util.tree_map(widen, state)
    c = _merge_states(1, aggs).fn.lower(side, side).compile()
    text = c.as_text()
    assert "jit_op_grouped_aggregate_merge" in text
    assert re.findall(r"\sconditional\(", text)
    assert not re.findall(r"\s(scatter|sort|gather)\(", text)
    assert c.memory_analysis() is not None


def _described(tree, lanes, one_chip):
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            (lanes,) + leaf.shape[1:], leaf.dtype, sharding=one_chip),
        tree)


@pytest.mark.parametrize("form,lanes,gathers", [
    ("compare", 1 << 21, 0), ("direct", N_BATCH, 1)])
def test_membership_probe_compiles_for_v5e_with_one_gather_or_none(
        one_chip, record_property, form, lanes, gathers):
    """``jit_op_semi_join_mask`` as TPC-H Q18 runs it (ISSUE 34): 2^21
    lineitem lanes against the 128-lane bucket that holds what the semi
    join left of orders compile to NO gather and no [lanes, keys]
    buffer (the compares reduce over the build axis inside their
    fusion); 2^20 lanes against a 2^24-slot direct table (the parent's
    layout for the same 128 lanes) to exactly ONE, where the parent's
    read lo_table and cnt_table."""
    import re
    import time
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.ops import join as J
    from presto_tpu.ops.jitcache import _semi
    small = Batch.from_pydict({"k": (T.BIGINT, [3, 1, 2])})
    build = _described(small, 128, one_chip)
    if form == "compare":
        prep = _described(J.prepare_build(small, [0]), 128, one_chip)
    else:
        los, sizes, lo_t, cnt_t, *rest = J.prepare_direct_keyed(
            small, [0], (0,), (4,), 4)
        prep = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=one_chip)
                     for x in (los, sizes)) + tuple(
            _described(x, 1 << 24, one_chip) for x in (lo_t, cnt_t)
        ) + tuple(_described(rest, 128, one_chip))
    assert J.lookup_form(prep) == form
    t = time.perf_counter()
    c = _semi((0,), (0,), False, False).fn.lower(
        _described(small, lanes, one_chip), build, prep).compile()
    record_property("compile_s", round(time.perf_counter() - t, 2))
    text = c.as_text()
    assert "jit_op_semi_join_mask" in text
    assert len(re.findall(r"\sgather\(", text)) == gathers
    assert not re.findall(r"\s(scatter|sort)\(", text)
    # nothing of lanes x keys is held: a few words a lane at most
    assert c.memory_analysis().temp_size_in_bytes <= 16 * lanes


@pytest.mark.parametrize("form,lanes,build_lanes", [
    ("composed", 1 << 15, 1 << 21), ("permuted", N_BATCH, N_SLOTS)])
def test_lookup_join_reads_the_payload_for_v5e_by_its_shapes(
        one_chip, record_property, form, lanes, build_lanes):
    """``jit_op_lookup_join`` as TPC-H Q3 runs it (ISSUE 36): 2^15
    lineitem lanes (what the join's cut leaves of a batch) against a
    `direct_keyed` build of orders at 2^21 lanes, its four columns the
    payload, compile to NO gather whose result has the build's lanes
    and exactly ten of the probe's: the lookup, the permutation, and
    four columns' data and validity (twelve in the program: a 64-bit
    column is read as two words). The reverse shapes (a 2^20-lane
    batch against 2^17 lanes: equal gather counts) keep the build-size
    permutation of every column: eight gathers of the build's lanes,
    nine of the probe's (ten and eleven in the program)."""
    import datetime
    import re
    import time
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.ops import join as J
    from presto_tpu.ops.jitcache import _lookup
    day = datetime.date(1995, 3, 15)
    orders = Batch.from_pydict({
        "o_orderkey": (T.BIGINT, [3, 1, 2]), "o_custkey": (T.BIGINT, [7, 8, 9]),
        "o_orderdate": (T.DATE, [day] * 3),
        "o_shippriority": (T.INTEGER, [0, 0, 0])})
    lines = Batch.from_pydict({
        "l_orderkey": (T.BIGINT, [1, 2, 5]),
        "l_extendedprice": (T.DOUBLE, [1.0, 2.0, 3.0]),
        "l_discount": (T.DOUBLE, [0.1, 0.0, 0.2])})
    prep = _described_prepared(
        J.prepare_direct_keyed(orders, [0], (1,), (4,), 4),
        1 << 23, build_lanes, one_chip)
    payload = (0, 1, 2, 3)
    assert J.payload_form(lanes, build_lanes, len(payload)) == form
    t = time.perf_counter()
    c = _lookup((0,), (0,), payload, ("$b0", "$b1", "$b2", "$b3"),
                "inner").fn.lower(
        _described(lines, lanes, one_chip),
        _described(orders, build_lanes, one_chip), prep).compile()
    record_property("compile_s", round(time.perf_counter() - t, 2))
    text = c.as_text()
    assert "jit_op_lookup_join" in text
    got = re.findall(r"= (\w+)\[(\d+)[\],][^=]*?\sgather\(", text)
    assert len(got) == len(re.findall(r"\sgather\(", text))
    # the v5e's compiler reads a 64-bit word as two of 32 bits: the two
    # BIGINT columns are four `u32` gathers, the DATE and the INTEGER
    # one `s32` each, the four validities `pred`
    columns = {"u32": 4, "s32": 2, "pred": 4}
    want = {(dt, lanes): n for dt, n in columns.items()}
    want["s32", lanes] += 1                         # the table's lookup
    if form == "composed":
        want["s32", lanes] += 1                     # the permutation
    else:
        want.update({(dt, build_lanes): n for dt, n in columns.items()})
    assert {k: got.count((k[0], str(k[1]))) for k in want} == want
    assert len(got) == sum(want.values())
    assert not re.findall(r"\s(scatter|sort)\(", text)


N_STATE = 1 << 24        # a summary of TPC-H SF10's 15M orders by key


def _q21_summary():
    """(a summary by key as TPC-H Q21's residual semi joins read it:
    order key, least and greatest supplier key; its direct layout, the
    build as it stands), tiny."""
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.ops import join as J
    summary = Batch.from_pydict({"k": (T.BIGINT, [3, 1, 2]),
                                 "lo": (T.BIGINT, [5, 5, 6]),
                                 "hi": (T.BIGINT, [9, 5, 7])})
    return summary, J.prepare_direct_keyed(summary, [0], (1,), (4,), 4,
                                           unique=True)


def _described_prepared(prepared, slots, lanes, one_chip):
    los, sizes, lo_t, cnt_t, *rest = prepared
    return tuple(jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
                 for x in (los, sizes)) + tuple(
        _described(x, slots, one_chip) for x in (lo_t, cnt_t)
    ) + tuple(_described(rest, lanes, one_chip))


def test_keyed_semi_build_compiles_for_v5e_without_a_gather(
        one_chip, record_property):
    """The filtering side of a `keyed` residual semi join at TPC-H
    SF10 (ISSUE 35): 2^24 lanes of (key, min, max), every key once. Its
    direct table addresses the build as it stands: no sort of the
    build's operands with a permutation behind it (the scatters that
    fill the two tables are the program's only sorts), no gather; the
    payload is packed with no gather and no sort. Compile seconds under
    120 (19 and 0.3 on this sandbox's CPU, alone)."""
    import re
    import time
    from presto_tpu.ops.jitcache import _pack_payload, _prepare_direct_keyed
    summary, prep = _q21_summary()
    build = _described(summary, N_STATE, one_chip)
    t = time.perf_counter()
    c = _prepare_direct_keyed((0,), (1,), (15_000_000,), N_STATE,
                              True).fn.lower(build).compile()
    text = c.as_text()
    assert "jit_op_prepare_direct_keyed" in text
    assert not re.findall(r"\sgather\(", text)
    assert len(re.findall(r"\ssort\(", text)) <= 2
    c = _pack_payload((1, 2), True).fn.lower(
        build, _described_prepared(prep, N_STATE, N_STATE,
                                   one_chip)).compile()
    seconds = time.perf_counter() - t
    record_property("compile_s", round(seconds, 2))
    text = c.as_text()
    assert "jit_op_pack_sorted_payload" in text
    assert not re.findall(r"\s(gather|scatter|sort)\(", text)
    assert seconds < 120


def test_keyed_semi_probe_compiles_for_v5e_with_two_gathers(
        one_chip, record_property):
    """One probe batch of TPC-H Q21's EXISTS (2^20 late lines against
    the 2^24-lane summary): ONE program, `jit_expr_semi_keyed_*`, that
    holds exactly two gathers (the direct table, then the packed words
    of the one match), no sort and no scatter, and nothing of lanes x
    matches: the m:n form gathered every probe and build column for
    each of 8 x 2^20 expanded lanes."""
    import re
    import time
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.exec.local import _residual_program
    from presto_tpu.expr import ir
    from presto_tpu.planner.plan import SemiJoinNode
    summary, prep = _q21_summary()
    probe = Batch.from_pydict({"k": (T.BIGINT, [1, 2, 3]),
                               "s": (T.BIGINT, [5, 6, 7])})
    supp = ir.input_ref(1, T.BIGINT)
    node = SemiJoinNode(
        source=None, filtering=None, source_keys=(0,), filtering_keys=(0,),
        fields=(), negated=True, null_aware=False, filtering_unique=True,
        residual=ir.special(
            ir.Form.OR, T.BOOLEAN,
            ir.call("ne", T.BOOLEAN, supp, ir.input_ref(3, T.BIGINT)),
            ir.call("ne", T.BOOLEAN, supp, ir.input_ref(4, T.BIGINT))))
    program = _residual_program("keyed", node, probe.schema)
    packed = jax.ShapeDtypeStruct((5, N_STATE), jnp.uint32,
                                  sharding=one_chip)
    t = time.perf_counter()
    c = program.fn.lower((
        _described(probe, N_BATCH, one_chip),
        _described(summary, N_STATE, one_chip),
        _described_prepared(prep, N_STATE, N_STATE, one_chip),
        packed)).compile()
    seconds = time.perf_counter() - t
    record_property("compile_s", round(seconds, 2))
    text = c.as_text()
    assert program.program in text
    assert program.program.startswith("jit_expr_semi_keyed_")
    assert len(re.findall(r"\sgather\(", text)) == 2
    assert not re.findall(r"\s(scatter|sort)\(", text)
    assert c.memory_analysis().temp_size_in_bytes <= 64 * N_BATCH
    assert seconds < 60


def test_summary_partial_lowers_without_a_scatter_for_v5e(one_chip):
    """The summary's partial group-by (`min`, `max` by key over a
    2^20-lane batch) as the TPU path lowers it: a min or a max of a run
    is a scan within the runs and the compress network, not the 64-bit
    segment scatter it was (0.13 s a batch a column on the v5e); the
    one sort is the branch for a batch out of key order. Lowered, not
    compiled: the program compiles for two minutes on this CPU (129 s,
    its compiled text then holds no scatter; my run, PR 35)."""
    from presto_tpu import types as T
    from presto_tpu.batch import Batch
    from presto_tpu.ops.aggregation import AggSpec
    from presto_tpu.ops.jitcache import _grouped
    rows = Batch.from_pydict({"k": (T.BIGINT, [1, 2, 2]),
                              "v": (T.BIGINT, [5, 6, 7])})
    aggs = (AggSpec("min", 1, T.BIGINT, "lo"),
            AggSpec("max", 1, T.BIGINT, "hi"))
    import re

    def ops(aggs):
        lowered = _grouped((0,), aggs, "partial", None, None,
                           True).fn.lower(_described(rows, N_BATCH, one_chip))
        return re.findall(r"stablehlo\.(\w+)", lowered.as_text())
    # (what is left are one-element updates of the boundary marks,
    # which the TPU compiler makes slices: TPC-H Q18's partial, a sum,
    # holds as many and compiles with none, PR 33)
    mine, sums = ops(aggs), ops((AggSpec("sum", 1, T.BIGINT, "s"),))
    assert mine.count("scatter") == sums.count("scatter") <= 4
    assert mine.count("sort") == 1 and "while" not in mine


def _probe_shapes(one_chip):
    i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32,  # noqa: E731
                                         sharding=one_chip)
    return [i32(N_BATCH)] + [i32(N_SLOTS)] * 5


def _probe(codes, lo, cnt, vb, p0, p1):
    return pallas_join.direct_probe(codes, lo, cnt, vb, [p0, p1],
                                    interpret=False)


def test_direct_probe_is_refused_for_v5e_and_off_by_default(one_chip):
    """The probe kernel's arbitrary-index gather from VMEM-resident
    tables does not lower on the installed JAX/libtpu, so the session
    property that routes joins through it must default to off. When a
    later JAX lowers it, this test fails: flip the default then (ISSUE
    23, ROADMAP 'decide the probe kernel from a trace')."""
    with pytest.raises(NotImplementedError, match="gather"):
        _compile(_probe, *_probe_shapes(one_chip))
    assert SESSION_PROPERTIES["join_pallas_probe"].default is False


def test_named_mesh_program_compiles_for_four_v5e_chips(topo):
    """A ``shard_map`` program built the way ``exec/distributed.py``
    builds its own (``ops/jitcache.named_jit`` around the mapped
    function) compiles for the 2x2 host with a cross-chip reduction in
    it, under the name the device trace will show."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from presto_tpu.ops.jitcache import named_jit
    mesh = Mesh(np.array(topo.devices), ("shards",))

    def partial_then_exchange(price, discount):
        local = jnp.sum(price * (1.0 - discount), keepdims=True)
        return jax.lax.psum(local, "shards")

    fn = named_jit("smap_agg_probe", shard_map(
        partial_then_exchange, mesh=mesh, in_specs=(P("shards"),) * 2,
        out_specs=P(), check_vma=False))
    rows = jax.ShapeDtypeStruct((N_BATCH,), jnp.float64,
                                sharding=NamedSharding(mesh, P("shards")))
    compiled = fn.lower(rows, rows).compile()
    text = compiled.as_text()
    assert "jit_smap_agg_probe" in text
    assert "all-reduce" in text


Q6 = """select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1994-01-01' + interval '1' year
      and l_discount between 0.06 - 0.01 and 0.06 + 0.01
      and l_quantity < 24"""
Q1 = """select l_returnflag, l_linestatus, sum(l_quantity), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus"""


@pytest.mark.parametrize("sql,banned", [
    # the discount's bounds were `cast(0.06 -+ 0.01 as double)`, a
    # division by 10^scale that the v5e rounds wrongly
    (Q6, ("divide",)),
    # the one bound was `date_add_days(lit, lit)`: now a compare with a
    # literal and nothing else
    (Q1, ("divide", "add", "subtract", "multiply", "convert")),
], ids=["q6", "q1"])
def test_filter_program_holds_no_literal_arithmetic_for_v5e(
        one_chip, monkeypatch, sql, banned):
    """ISSUE 29: literal expressions are folded on the host at plan
    time, so the filter program of TPC-H Q6 and of Q1, lowered for the
    described v5e at the SQL path's batch size, computes nothing from
    its literals (the lowered StableHLO text), and compiles."""
    from presto_tpu.exec import local as local_exec
    from presto_tpu.exec.runner import LocalRunner
    from presto_tpu.expr.compiler import ExprCompiler
    from presto_tpu.planner.plan import FilterNode
    seen = {}
    real = local_exec._Executor._TableScanNode

    def capture(self, node):
        for b in real(self, node):
            seen.setdefault("batch", b)
            yield b
    monkeypatch.setattr(local_exec._Executor, "_TableScanNode", capture)
    runner = LocalRunner(tpch_sf=0.002)
    runner.execute(sql)
    node = runner.plan(sql).root
    while not isinstance(node, FilterNode):
        [node] = node.children
    program = ExprCompiler().filter(
        node.predicate, local_exec._plan_schema(node.child), errors=True)

    def widened(leaf):
        return jax.ShapeDtypeStruct((N_BATCH,) + leaf.shape[1:],
                                    leaf.dtype, sharding=one_chip)
    lowered = program.fn.lower(
        jax.tree_util.tree_map(widened, seen["batch"]))
    ops = {line.split("stablehlo.")[1].split()[0].strip('"')
           for line in lowered.as_text().splitlines()
           if "stablehlo." in line}
    assert "compare" in ops
    assert not ops & set(banned), sorted(ops)
    assert program.program in lowered.compile().as_text()
