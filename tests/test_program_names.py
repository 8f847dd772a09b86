"""Names, spans and counters of what runs on the device (PR 26).

- every program the engine jits has a stable name under one of three
  prefixes (``op_``, ``expr_``, ``smap_``), the same in two processes
  started with different ``PYTHONHASHSEED``;
- while the tracer is on, the engine's spans stand in a profiler trace
  (``.xplane.pb``) as annotations nested query > op:* > dispatch /
  device-sync; while it is off, nothing is constructed;
- compiles are counted from JAX's own event (a retrace counts), syncs
  and expression-program launches where they happen.
"""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs import trace as obs_trace
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.profiler import EXECUTABLES
from presto_tpu.obs.trace import NOOP_SPAN, TRACER

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

JOIN_GROUP_BY = (
    "select o_orderpriority, count(*), sum(o_totalprice) from orders "
    "join customer on o_custkey = c_custkey where c_acctbal > 0 "
    "group by 1")
MESH_GROUP_BY = (
    "select l_returnflag, sum(l_quantity) from lineitem "
    "where l_shipdate <= date '1998-09-02' group by 1")

#: runs the two queries (the second on a mesh of 4 virtual devices) and
#: prints {record name: [the program names its records carry]}
NAMES_SCRIPT = f"""
import json, sys
sys.path.insert(0, {_REPO!r})
import presto_tpu
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.profiler import EXECUTABLES
r = LocalRunner(tpch_sf=0.01, rows_per_batch=8192)
r.execute({JOIN_GROUP_BY!r}, properties={{"mesh_execution": "off"}})
r.execute({MESH_GROUP_BY!r},
          properties={{"mesh_execution": "on", "mesh_devices": "4"}})
# a split's 7,500 orders in chunks of 5,000: the second, at 4,096 lanes,
# is padded to the first's 8,192 (`pad_capacity`; lineitem's chunks no
# longer pad here: since PR 35 none outgrows rows_per_batch)
LocalRunner(tpch_sf=0.01, rows_per_batch=5000).execute(
    "select count(*) from orders", properties={{"mesh_execution": "off"}})
out = {{}}
for rec in EXECUTABLES._records.values():
    if rec.invocations:
        out.setdefault(rec.name, set()).add(rec.fun_name)
print(json.dumps({{k: sorted(v) for k, v in out.items()}}))
"""

#: the entries such a query must build: jit-cache entries by name,
#: expression programs and mesh programs by what their names start with
ENTRIES = ["grouped_aggregate", "lookup_join", "build_summary",
           "prepare_direct_keyed", "key_bounds_violation", "pad_capacity",
           "expr_filter_", "expr_project_", "smap:"]


@pytest.fixture(scope="module")
def names_by_hashseed():
    out = []
    for seed in ("1", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        p = subprocess.run([sys.executable, "-c", NAMES_SCRIPT], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    return out


@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_has_a_prefixed_name_equal_across_hash_seeds(
        names_by_hashseed, entry):
    first, second = names_by_hashseed
    mine = {k: v for k, v in first.items() if k.startswith(entry)}
    assert mine, f"no record {entry}* among {sorted(first)}"
    for record, programs in mine.items():
        assert second.get(record) == programs, record
        for fun_name in programs:
            assert re.fullmatch(r"jit\((op|expr|smap)_[a-z0-9_]+\)",
                                fun_name), (record, fun_name)
        if entry.startswith("expr_"):
            # the record IS the program: expr_<kind>_<six hex digits>
            assert programs == [f"jit({record})"]
            assert re.fullmatch(r"expr_(filter|project)_[0-9a-f]{6}",
                                record)
        elif entry == "smap:":
            assert all(p.startswith("jit(smap_") for p in programs)
        else:
            assert programs == [f"jit(op_{record})"]


def test_no_engine_record_is_left_unnamed(names_by_hashseed):
    first, second = names_by_hashseed
    assert first == second
    for record, programs in first.items():
        for fun_name in programs:
            assert not fun_name.startswith(("jit(run", "jit(<lambda>")), \
                (record, fun_name)


def test_named_jit_refuses_a_name_outside_the_three_prefixes():
    from presto_tpu.ops.jitcache import named_jit, program_name
    with pytest.raises(ValueError):
        named_jit("run", lambda x: x)
    with pytest.raises(ValueError):
        named_jit("op_Bad-Name", lambda x: x)
    assert program_name("smap", "agg__AggregationNode_<lambda>") \
        == "smap_agg_aggregationnode_lambda"
    fn = named_jit("op_probe_name", lambda x: x + 1)
    import jax.numpy as jnp
    assert "@jit_op_probe_name" in fn.lower(jnp.arange(4)).as_text()


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.01, rows_per_batch=8192)


@pytest.fixture
def tracer_on():
    TRACER.clear()
    TRACER.enable(True)
    try:
        yield TRACER
    finally:
        TRACER.enable(False)
        TRACER.clear()


def _events(path):
    """{name: [(start, end, {stat: value})]} of the host plane."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     {k: v for k, v in e.stats}))
    return out


def _inside(inner, outers):
    return any(a <= inner[0] and inner[1] <= b for a, b, _ in outers)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_profile_holds_the_engines_spans_nested(runner, tracer_on,
                                                tmp_path):
    import jax
    props = {"mesh_execution": "off"}
    runner.execute(JOIN_GROUP_BY, properties=props)      # compile first
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        runner.execute(JOIN_GROUP_BY, properties=props)
    finally:
        jax.profiler.stop_trace()
    planes = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    assert len(planes) == 1
    ev = _events(planes[0])
    assert len(ev["query"]) == 1 and len(ev["plan"]) == 1
    ops = [iv for name, ivs in ev.items() if name.startswith("op:")
           for iv in ivs]
    assert {"op:Join", "op:Aggregation", "op:TableScan"} <= set(ev)
    # query > op:* > dispatch, and query > op:* > device-sync
    assert all(_inside(op, ev["query"]) for op in ops)
    assert all(_inside(d, ops) for d in ev["dispatch"])
    assert any(_inside(s, ops) for s in ev["device-sync"])
    assert all(_inside(s, ev["query"]) for s in ev["device-sync"])
    programs = {d[2]["program"] for d in ev["dispatch"]}
    assert "jit_op_lookup_join" in programs
    assert any(p.startswith("jit_expr_filter_") for p in programs)
    whats = {s[2]["what"] for s in ev["device-sync"]}
    assert {"build-summary", "result"} <= whats
    # the tracer's own spans carry the same names and parentage
    spans = TRACER.export()
    by_id = {s["spanId"]: s for s in spans}
    for s in spans:
        if s["name"] == "dispatch":
            # a launch made while a sync waits is the sync's child, one
            # made by a merge of the grouped state the merge's
            parent = by_id[s["parentId"]]["name"]
            assert parent.startswith("op:") \
                or parent in ("device-sync", "agg-merge")


def test_tracer_off_constructs_nothing(runner, monkeypatch):
    made = []
    real = obs_trace._annotation
    monkeypatch.setattr(obs_trace, "_annotation",
                        lambda *a: made.append(a) or real(*a))
    assert not TRACER.enabled
    assert TRACER.span("dispatch", program="x") is NOOP_SPAN
    assert TRACER.wrap_iter("op:X", iter(())).__class__ is type(iter(()))
    runner.execute(MESH_GROUP_BY, properties={"mesh_execution": "off"})
    assert made == [] and TRACER.export() == []
    TRACER.enable(True)
    try:
        runner.execute(MESH_GROUP_BY, properties={"mesh_execution": "off"})
    finally:
        TRACER.enable(False)
        TRACER.clear()
    assert {a[0] for a in made} >= {"query", "plan", "dispatch",
                                    "device-sync", "op:Aggregation"}


def _value(name):
    return REGISTRY.value(name, default=0.0)


def test_a_retrace_is_a_compile_the_listener_counts():
    """The first-call stopwatch this replaces saw one compile: the
    second shape bucket retraced silently."""
    import jax.numpy as jnp

    from presto_tpu.ops.jitcache import timed_entry
    entry = timed_entry("retrace_probe", lambda x: x * 2 + 1)
    before = {n: _value(n) for n in ("xla_compile_total",
                                     "jit_compile_total")}
    entry(jnp.arange(4))
    assert entry.record.compiles == 1
    entry(jnp.arange(4))
    assert entry.record.compiles == 1       # same bucket: no compile
    entry(jnp.arange(8))                    # a new bucket retraces
    assert entry.record.compiles == 2
    assert entry.record.invocations == 3
    assert entry.record.compile_seconds > 0.0
    assert _value("jit_compile_total") - before["jit_compile_total"] == 2
    # eager ops (the aranges) land under xla_compile_* only
    assert _value("xla_compile_total") - before["xla_compile_total"] >= 2
    assert _value("xla_compile_seconds_total") \
        >= _value("jit_compile_seconds_total") > 0.0
    row = next(r for r in EXECUTABLES.snapshot(analyze=False)
               if r["name"] == "retrace_probe")
    assert row["compiles"] == 2


def test_compile_span_is_recorded_with_its_duration(tracer_on):
    import jax.numpy as jnp

    from presto_tpu.ops.jitcache import timed_entry
    entry = timed_entry("compile_span_probe", lambda x: x - 3)
    entry(jnp.arange(16))
    spans = {s["name"]: s for s in TRACER.export()
             if s["attrs"].get("program", "").endswith(
                 "op_compile_span_probe)")
             or s["attrs"].get("program") == "jit_op_compile_span_probe"}
    assert set(spans) == {"compile", "dispatch"}
    c, d = spans["compile"], spans["dispatch"]
    assert c["parentId"] == d["spanId"]
    assert d["start"] <= c["start"] < c["end"] <= d["end"] + 1e-3
    assert c["end"] - c["start"] == pytest.approx(c["attrs"]["seconds"],
                                                  abs=1e-5)


def test_syncs_and_expression_launches_move_by_what_the_query_does(
        runner, tracer_on):
    """A filter and a projection over lineitem in 8192-row batches:
    three expression programs a batch (the filter, the column pruning
    under it, the projection), no jit-cache entry, and one ``result``
    sync for each batch of the answer."""
    sql = "select l_orderkey + 1 from lineitem where l_quantity < 5"
    props = {"mesh_execution": "off"}
    runner.execute(sql, properties=props)
    TRACER.clear()
    names = ("device_sync_total.result",
             "device_sync_seconds_total.result",
             "expr_program_invocations_total",
             "jit_cache_invocations_total")
    before = {n: _value(n) for n in names}
    rows = runner.execute(sql, properties=props).rows
    moved = {n: _value(n) - before[n] for n in names}
    spans = TRACER.export()
    scan = next(s for s in spans if s["name"] == "op:TableScan")
    batches = scan["attrs"]["batches"]
    assert batches == -(-60175 // 8192) and len(rows) > 0
    assert moved["expr_program_invocations_total"] == 3 * batches
    assert moved["jit_cache_invocations_total"] == 0
    assert moved["device_sync_total.result"] == batches
    assert moved["device_sync_seconds_total.result"] > 0.0
    assert sum(s["name"] == "dispatch" for s in spans) == 3 * batches
    syncs = [s for s in spans if s["name"] == "device-sync"]
    assert [s["attrs"]["what"] for s in syncs] == ["result"] * batches
