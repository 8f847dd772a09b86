"""The benchmark's own data and references, held to the program's: the
generator column by column, each template's answer at the literals it
shares with ``tpch_reference.py``, Q6's decimal bounds against the
engine, and the float32 control, which has to come out as not correct."""
import random

import numpy as np
import pytest

from conftest import SMALL_SF

import harness
import tpchdata

SHARED = {
    "q6": {"DATE": "1994-01-01", "DISCOUNT": "0.06", "QUANTITY": "24"},
    "q1": {"DELTA": "90"},
    "q3": {"SEGMENT": "BUILDING", "DATE": "1995-03-15"},
}


def _template(name):
    return harness._module("templates", name)


@pytest.fixture(scope="module")
def conn():
    import presto_tpu  # noqa: F401
    from presto_tpu.connectors.tpch import TpchConnector
    return TpchConnector(sf=SMALL_SF)


@pytest.mark.parametrize("table", ["lineitem", "orders", "customer"])
def test_own_generator_equals_the_connectors(conn, table):
    import tpch_reference as R
    n = tpchdata.row_counts(SMALL_SF)
    mine = {"lineitem": lambda: tpchdata.lineitem(SMALL_SF, 1,
                                                  n["orders"] + 1),
            "orders": lambda: tpchdata.orders(SMALL_SF, 1, n["orders"] + 1),
            "customer": lambda: tpchdata.customer(SMALL_SF, 1,
                                                  n["customer"] + 1)}[table]()
    cols = [c for c in mine if not c.endswith(("_pct", "_int"))]
    chunks, rows, _, vocabs = R.stage_host(conn, table, cols)
    assert rows == len(mine[cols[0]])
    if table == "lineitem":
        assert rows == tpchdata.lineitem_rows(SMALL_SF)
    for i, c in enumerate(cols):
        theirs = np.concatenate([ch[i] for ch in chunks])
        assert np.array_equal(theirs, mine[c]), c
    names = dict(zip(cols, vocabs))
    for c, vocab in (("l_returnflag", tpchdata.RETURN_FLAGS),
                     ("l_linestatus", tpchdata.LINE_STATUS),
                     ("c_mktsegment", tpchdata.SEGMENTS)):
        if c in names:
            assert names[c] == vocab


def test_references_equal_the_programs_at_the_shared_literals(conn):
    import chip_smoke
    want = chip_smoke.reference_answers(conn)
    got = {q: _template(q).reference(tpchdata, SMALL_SF, [SHARED[q]])[0]
           for q in SHARED}
    assert harness.compare_rows(_template("q6").KINDS, got["q6"],
                                [(want["q6"],)]) == (pytest.approx(0, abs=1e-14), 0)
    for q in ("q1", "q3"):
        gap, wrong = harness.compare_rows(_template(q).KINDS, got[q],
                                          [tuple(r) for r in want[q]])
        assert wrong == 0 and gap < 1e-14, (q, gap, wrong)


def test_q6_every_discount_of_the_range_against_the_engine():
    """`between D - 0.01 and D + 0.01` is decimal arithmetic in SQL; the
    reference forms the bounds with ``decimal``. Every DISCOUNT the rule
    can draw, against the engine itself."""
    import presto_tpu  # noqa: F401
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    q6 = _template("q6")
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=SMALL_SF))
    runner = LocalRunner(catalogs=catalogs, catalog="tpch")
    bindings = [{"DATE": "1995-01-01", "DISCOUNT": f"0.0{d}",
                 "QUANTITY": str(24 + d % 2)} for d in range(2, 10)]
    answers = q6.reference(tpchdata, SMALL_SF, bindings)
    for b, want in zip(bindings, answers):
        rows = runner.execute(q6.SQL.format(**b)).rows
        gap, wrong = harness.compare_rows(q6.KINDS, rows, want)
        assert wrong == 0 and gap <= q6.DOUBLE_REL_LIMIT, (b, gap)


@pytest.mark.parametrize("name", sorted(SHARED))
def test_draws_are_in_range_and_fit_any_seed(name):
    t = _template(name)
    for seed in (0, 7, 2 ** 31 + 11, 2 ** 33 + 5):
        traffic = {"bindings": 2}
        a = harness.draw_bindings(t, traffic, seed)
        assert a == harness.draw_bindings(t, traffic, seed)
        assert len(a) == 2 and a[0] != a[1]
        for b in a:
            assert set(b) == set(t.ASSUMED)
            assert "{" not in t.SQL.format(**b)


@pytest.mark.parametrize("name", sorted(SHARED))
def test_the_float32_control_comes_out_not_correct(name):
    """The control: the reference in the program's place, computed in
    the precision below the one the configuration states (float32 for
    DOUBLE). It has to fail the limit, on every seed tried."""
    t = _template(name)
    for seed in (1, 2, 3):
        bindings = harness.draw_bindings(t, {"bindings": 2}, seed)
        want = t.reference(tpchdata, SMALL_SF, bindings)
        control = t.reference(tpchdata, SMALL_SF, bindings, np.float32)
        gaps = [harness.compare_rows(t.KINDS, c, w)[0]
                for c, w in zip(control, want)]
        assert max(gaps) > 3 * t.DOUBLE_REL_LIMIT, (seed, gaps)
