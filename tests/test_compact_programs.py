"""No compaction outside a jit (PR 30): TPC-H Q6 and Q3 through
``LocalRunner``'s executor compact their batches in ONE program,
``jit_op_compact`` (``ops/jitcache.compact_jit``), never through the
eager ``Batch.compact`` that cost Q6 6.1 of its 6.5 s on the v5e.

Q6 at SF0.1 with 2^18-row batches (the ``_compactor`` looks at no batch
of 2^17 lanes or fewer), Q3 at SF0.01 with 8192-row batches and
``fused_compact_floor`` 1, so that the fused chain compacts too: the
sizes the benchmark's rehearsals and ``test_dense_group.py`` use. The
answers are held to the benchmark's own NumPy references
(``benchmarks/templates``), which share nothing with the program.

What the listener may still see from ``batch.py``: ``Batch.count`` under
the liveness readback (the host needs the count: it stays) and
``concat_batches``; neither is a compaction.
"""
import importlib.util
import os
import sys
import traceback

import jax
import jax.monitoring
import pytest

from presto_tpu import batch as batch_mod
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.profiler import COMPILE_EVENT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
#: the compaction's frames in ``batch.py``
COMPACTION = {"compact", "live_indices", "_rows_cumsum"}

CASES = {
    "q6": dict(sf=0.1, rows_per_batch=1 << 18, properties={},
               binding={"DATE": "1994-01-01", "DISCOUNT": "0.06",
                        "QUANTITY": "24"}),
    "q3": dict(sf=0.01, rows_per_batch=8192,
               properties={"fused_compact_floor": 1,
                           "fused_compact_window": 2},
               binding={"SEGMENT": "BUILDING", "DATE": "1995-03-15"}),
}


def _template(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"bench_templates_{name}",
        os.path.join(BENCH, "templates", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def watched(monkeypatch):
    """What the statement compiled eagerly from inside a compaction, and
    every ``Batch.compact`` it ran outside a trace: both must stay
    empty. (JAX compiles an eager op once a process, so the second list
    is the one that cannot pass by an earlier test's doing.)"""
    eager_compiles, eager_calls = [], []
    pkg = os.path.dirname(os.path.abspath(batch_mod.__file__))

    def on_compile(event, duration, **kw):
        name = kw.get("fun_name", "?")
        if event != COMPILE_EVENT or name.startswith(
                ("jit(op_", "jit(expr_", "jit(smap_")):
            return
        if any(f.filename == os.path.join(pkg, "batch.py")
               and f.name in COMPACTION for f in traceback.extract_stack()):
            eager_compiles.append(name)

    compact = batch_mod.Batch.compact

    def spied(self, capacity=None, *, check=True):
        if not isinstance(self.row_mask, jax.core.Tracer):
            eager_calls.append(traceback.extract_stack()[-2])
        return compact(self, capacity, check=check)

    monkeypatch.setattr(batch_mod.Batch, "compact", spied)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        yield eager_compiles, eager_calls
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)


@pytest.mark.parametrize("name", list(CASES))
def test_a_statement_compacts_in_one_program_only(name, watched):
    case = CASES[name]
    template = _template(name)
    import tpchdata
    from harness import compare_rows
    runner = LocalRunner(tpch_sf=case["sf"],
                         rows_per_batch=case["rows_per_batch"])
    names = ("compact_programs_total", "compact_applied_total",
             "compact_checked_total")
    before = {n: REGISTRY.value(n) for n in names}
    rows = runner.execute(template.SQL.format(**case["binding"]),
                          properties=case["properties"]).rows
    programs, applied, checked = (REGISTRY.value(n) - before[n]
                                  for n in names)
    eager_compiles, eager_calls = watched
    assert eager_compiles == [] and eager_calls == []
    [want] = template.reference(tpchdata, case["sf"], [case["binding"]])
    gap, wrong = compare_rows(template.KINDS, [list(r) for r in rows], want)
    assert wrong == 0 and gap <= template.DOUBLE_REL_LIMIT
    if name == "q6":
        # every batch of the 1.9 % filter's output shrinks, and nothing
        # else in the plan compacts
        assert checked >= 600572 // (1 << 18)      # lineitem's rows
        assert programs == applied == checked
    else:
        # no batch is over the _compactor's floor; the fused chain, the
        # build sides and TopN launch the program all the same
        assert applied == 0 and programs >= 2
