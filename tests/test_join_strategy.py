"""Stats-driven join strategy selection + the Pallas probe kernel.

The direct-address paths (single-key measured, multi-key planner-keyed,
and the Pallas probe kernel over either) must be RESULT-IDENTICAL to
the sorted-lookup path for every key shape the planner can route to
them — NULL keys, negative keys, keys sitting exactly on their stats
bounds, out-of-domain probe keys, composite key tuples, duplicate
(expansion) builds — because the dispatch is a pure performance
decision. Bounds that LIE (a live build key outside the planner's
promise) must fail the query with STATS_BOUND_VIOLATION, never drop
matches (the dense-grouping contract applied to joins)."""
import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, Schema
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.ops import join as J
from presto_tpu.ops import pallas_join as PJ


def _metric(name: str) -> float:
    for m in REGISTRY.snapshot():
        if m["name"] == name:
            return float(m.get("value", 0.0))
    return 0.0


def _sorted_rows(rows):
    return sorted(rows, key=lambda t: tuple((v is None, str(type(v)), v)
                                            for v in t))


def _rows(batch):
    return _sorted_rows([tuple(r) for r in batch.to_pylist()])


def _with_nulls(b: Batch, col: int, null_rows) -> Batch:
    cols = list(b.columns)
    mask = np.ones(b.capacity, dtype=bool)
    mask[list(null_rows)] = False
    c = cols[col]
    cols[col] = Column(c.type, c.data,
                       c.validity & jnp.asarray(mask), c.dictionary)
    return Batch(b.schema, cols, b.row_mask)


def _build(keys1, keys2, vals):
    return Batch.from_pydict({
        "k1": (T.BIGINT, keys1), "k2": (T.BIGINT, keys2),
        "v": (T.BIGINT, vals)})


# ---------------------------------------------------------------------------
# kernel parity: keyed direct vs sorted
# ---------------------------------------------------------------------------

def test_direct_keyed_vs_sorted_parity_random():
    rng = np.random.default_rng(7)
    n, m = 300, 500
    b1 = rng.integers(-20, 20, n).tolist()
    b2 = rng.integers(5, 12, n).tolist()
    build = _build(b1, b2, list(range(n)))
    build = _with_nulls(build, 0, [3, 50])
    probe = Batch.from_pydict({
        "p1": (T.BIGINT, rng.integers(-25, 25, m).tolist()),
        "p2": (T.BIGINT, rng.integers(3, 14, m).tolist()),
        "x": (T.BIGINT, list(range(m)))})
    probe = _with_nulls(probe, 1, [0, 7, 100])
    bounds = ((-20, 19), (5, 11))
    los, sizes, K = J.direct_keyed_plan(bounds)
    keyed = J.prepare_direct_keyed(build, [0, 1], los, sizes, K)
    sortp = J.prepare_build(build, [0, 1])
    # duplicates exist -> expansion join; parity across both tables
    for jt in ("inner", "left"):
        a = J.expand_join(probe, build, [0, 1], [0, 1], [2], ["v"], jt,
                          8, prepared=keyed)
        c = J.expand_join(probe, build, [0, 1], [0, 1], [2], ["v"], jt,
                          8, prepared=sortp)
        assert _rows(a) == _rows(c), jt
    assert int(J.max_multiplicity(keyed)) == int(J.max_multiplicity(sortp))
    for neg in (False, True):
        ma = J.semi_join_mask(probe, build, [0, 1], [0, 1], neg, False,
                              prepared=keyed)
        mc = J.semi_join_mask(probe, build, [0, 1], [0, 1], neg, False,
                              prepared=sortp)
        assert bool(jnp.all(ma == mc)), neg


def test_direct_keyed_bound_edges_and_out_of_domain():
    """Keys exactly on lo/hi match; probe keys outside the promised
    domain (which provably cannot match an in-bounds build) miss."""
    build = Batch.from_pydict({
        "k": (T.BIGINT, [-5, 0, 7]), "v": (T.BIGINT, [1, 2, 3])})
    los, sizes, K = J.direct_keyed_plan(((-5, 7),))
    keyed = J.prepare_direct_keyed(build, [0], los, sizes, K)
    probe = Batch.from_pydict({
        "p": (T.BIGINT, [-5, 7, -6, 8, 0, None])})
    out = J.lookup_join(probe, build, [0], [0], [1], ["v"], "inner",
                        prepared=keyed)
    assert _rows(out) == [(-5, 1), (0, 2), (7, 3)]
    left = J.lookup_join(probe, build, [0], [0], [1], ["v"], "left",
                         prepared=keyed)
    assert len(_rows(left)) == 6


def test_direct_keyed_plan_gates():
    assert J.direct_keyed_plan(()) is None
    assert J.direct_keyed_plan((None,)) is None
    assert J.direct_keyed_plan(((5, 4),)) is None          # empty span
    big = 1 << 20
    assert J.direct_keyed_plan(((0, big), (0, big))) is None  # product
    plan = J.direct_keyed_plan(((0, 9), (0, 9)))
    assert plan == ((0, 0), (10, 10), 100)


# ---------------------------------------------------------------------------
# the point lookup's three forms against the binary search
# ---------------------------------------------------------------------------

I64_MAX = int(np.iinfo(np.int64).max)


def _case(name):
    """(build, probe, key columns, planner-style bounds or None,
    unique build keys) of one parity case; build column -1 is a payload,
    probe column -1 a row id."""
    rng = np.random.default_rng(34)
    L = J.COMPARE_ALL_LIMIT
    if name == "ints":
        # NULL and negative keys, keys ON the bounds, probe keys outside
        bk = [-5, 0, 7, 3, -2, 4, 6]
        build = _with_nulls(Batch.from_pydict({
            "k": (T.BIGINT, bk), "v": (T.BIGINT, list(range(len(bk))))}),
            0, [3])
        probe = _with_nulls(Batch.from_pydict({
            "p": (T.BIGINT, [-5, 7, -6, 8, 0, 1, 3, -2, 100, -100, 4, 4]),
            "x": (T.BIGINT, list(range(12)))}), 0, [5])
        return build, probe, [0], ((-5, 7),), True
    if name == "int64_max":
        # the dead rows' sentinel IS int64-max: a live max key matches,
        # and where the build has none a max probe key matches nothing
        bk = [I64_MAX - 3, I64_MAX, I64_MAX - 1, I64_MAX - 7]
        build = Batch.from_pydict({
            "k": (T.BIGINT, bk), "v": (T.BIGINT, [1, 2, 3, 4])},
            capacity=16)
        probe = Batch.from_pydict({
            "p": (T.BIGINT, [I64_MAX, I64_MAX - 1, I64_MAX - 2,
                             I64_MAX - 7, I64_MAX - 8, None]),
            "x": (T.BIGINT, list(range(6)))})
        return build, probe, [0], ((I64_MAX - 7, I64_MAX),), True
    if name == "no_max_in_build":
        build = Batch.from_pydict({
            "k": (T.BIGINT, [1, 2, 3]), "v": (T.BIGINT, [1, 2, 3])},
            capacity=8)
        probe = Batch.from_pydict({
            "p": (T.BIGINT, [I64_MAX, 3, -I64_MAX - 1, 0]),
            "x": (T.BIGINT, list(range(4)))})
        return build, probe, [0], None, True
    if name == "double":
        # -0.0 joins +0.0; the u64 total-order operands
        build = Batch.from_pydict({
            "k": (T.DOUBLE, [-0.0, 1.5, -2.25, 1e300, None]),
            "v": (T.BIGINT, [1, 2, 3, 4, 5])})
        probe = Batch.from_pydict({
            "p": (T.DOUBLE, [0.0, -0.0, 1.5, 2.25, -2.25, 1e300, -1e300,
                             None]),
            "x": (T.BIGINT, list(range(8)))})
        return build, probe, [0], None, True
    if name == "tuple":
        pairs = sorted({(int(a), int(b)) for a, b in zip(
            rng.integers(-20, 20, 300), rng.integers(5, 12, 300))})
        build = _with_nulls(_build([a for a, _ in pairs],
                                   [b for _, b in pairs],
                                   list(range(len(pairs)))), 1, [3, 50])
        probe = _with_nulls(Batch.from_pydict({
            "p1": (T.BIGINT, rng.integers(-25, 25, 500).tolist()),
            "p2": (T.BIGINT, rng.integers(3, 14, 500).tolist()),
            "x": (T.BIGINT, list(range(500)))}), 1, [0, 7, 100])
        return build, probe, [0, 1], ((-20, 19), (5, 11)), True
    if name == "duplicates":
        bk = rng.integers(-8, 8, 200).tolist()
        build = _with_nulls(Batch.from_pydict({
            "k": (T.BIGINT, bk), "v": (T.BIGINT, list(range(200)))}),
            0, [1, 2])
        probe = _with_nulls(Batch.from_pydict({
            "p": (T.BIGINT, rng.integers(-12, 12, 300).tolist()),
            "x": (T.BIGINT, list(range(300)))}), 0, [9])
        return build, probe, [0], ((-8, 7),), False
    # a build of exactly the limit's lanes (the last compare-all shape)
    # and of twice as many (the first that searches), a few lanes dead
    cap = L if name == "at_limit" else 2 * L
    bk = rng.permutation(4 * cap)[:cap - 5] - cap
    build = Batch.from_pydict({
        "k": (T.BIGINT, bk.tolist()),
        "v": (T.BIGINT, list(range(cap - 5)))}, capacity=cap)
    probe = Batch.from_pydict({
        "p": (T.BIGINT, (rng.integers(0, 5 * cap, 700) - cap - 7).tolist()),
        "x": (T.BIGINT, list(range(700)))})
    return build, probe, [0], ((-cap, 3 * cap - 1),), True


def _prepared(form, build, keys, bounds):
    if form == "direct":
        lo, hi = bounds[0]
        return J.prepare_direct(build, keys, lo, hi - lo + 1)
    if form == "keyed":
        los, sizes, K = J.direct_keyed_plan(bounds)
        return J.prepare_direct_keyed(build, keys, los, sizes, K)
    return J.prepare_build(build, keys)


def _answers(probe, build, keys, prepared, unique, empty):
    """Everything that goes through ``_point_lookup``, as plain values."""
    out = {}
    pay = [len(build.columns) - 1]
    if unique:
        for jt in ("inner", "left"):
            out[jt] = _rows(J.lookup_join(probe, build, keys, keys, pay,
                                          ["v"], jt, prepared=prepared))
        survived = jnp.arange(probe.capacity) % 3 != 0
        out["visited"] = np.asarray(J.unique_match_build_mask(
            probe, build, keys, keys, survived,
            prepared=prepared)).tolist()
    for tag, negated, null_aware in (("in", False, True),
                                     ("not_in", True, True),
                                     ("not_exists", True, False)):
        out[tag] = np.asarray(J.semi_join_mask(
            probe, build, keys, keys, negated, null_aware,
            prepared=prepared)).tolist()
    # NOT IN against an EMPTY build keeps every probe row, NULL keys too
    out["not_in_empty"] = np.asarray(J.semi_join_mask(
        probe, empty[0], keys, keys, True, True,
        prepared=empty[1])).tolist()
    return out


@pytest.mark.parametrize("case,form", [
    ("ints", "direct"), ("ints", "keyed"), ("ints", "compare"),
    ("int64_max", "direct"), ("int64_max", "keyed"),
    ("int64_max", "compare"), ("no_max_in_build", "compare"),
    ("double", "compare"),
    ("tuple", "keyed"), ("tuple", "compare"),
    ("duplicates", "direct"), ("duplicates", "keyed"),
    ("duplicates", "compare"),
    ("at_limit", "direct"), ("at_limit", "keyed"),
    ("at_limit", "compare"),
    ("twice_limit", "direct"), ("twice_limit", "keyed"),
    ("twice_limit", "sorted"),
])
def test_point_lookup_forms_match_the_binary_search(monkeypatch, case,
                                                    form):
    """ISSUE 34: a probe lane learns (pos, hit) from ONE gather into a
    direct table (either layout) or, of a sorted build at or under
    COMPARE_ALL_LIMIT, from no gather at all; every caller of
    ``_point_lookup`` answers as it does over the binary search."""
    build, probe, keys, bounds, unique = _case(case)
    dead = Batch(build.schema, build.columns,
                 jnp.zeros_like(build.row_mask))
    prepared = _prepared(form, build, keys, bounds)
    assert J.lookup_form(prepared) == {"keyed": "direct"}.get(form, form)
    got = _answers(probe, build, keys, prepared, unique,
                   (dead, _prepared(form, dead, keys, bounds)))
    with monkeypatch.context() as m:
        m.setattr(J, "COMPARE_ALL_LIMIT", 0)     # every lane searches
        ref = J.prepare_build(build, keys)
        assert J.lookup_form(ref) == "sorted"
        want = _answers(probe, build, keys, ref, unique,
                        (dead, J.prepare_build(dead, keys)))
    assert got == want
    assert any(want["in"]) and not all(want["in"])
    assert want["not_in_empty"] == np.asarray(probe.row_mask).tolist()
    if case == "ints":
        # a NULL in the build: NOT IN passes nothing
        assert not any(want["not_in"]) and any(want["not_exists"])


@pytest.mark.parametrize("layout", ["direct", "keyed"])
def test_direct_tables_say_taken_in_lo_alone(layout):
    """What the one-gather lookup rests on: inside the table a slot's
    ``lo < n`` exactly where its ``cnt > 0``, with dead rows, NULL keys,
    duplicate keys and (keyed) live rows OUTSIDE the promised bounds,
    which go to the overflow slot with the dead."""
    rng = np.random.default_rng(5)
    build = _with_nulls(_build(rng.integers(-6, 30, 200).tolist(),
                               rng.integers(0, 9, 200).tolist(),
                               list(range(200))), 0, [4, 5, 6])
    mask = np.ones(build.capacity, dtype=bool)
    mask[[0, 17, 199]] = False
    build = Batch(build.schema, build.columns,
                  build.row_mask & jnp.asarray(mask))
    if layout == "direct":
        prep = J.prepare_direct(build, [0], -6, 64)
        lo_t, cnt_t = prep[1], prep[2]
    else:
        # k1's promise (0..20) is broken by live rows on both sides
        los, sizes, K = J.direct_keyed_plan(((0, 20), (0, 8)))
        prep = J.prepare_direct_keyed(build, [0, 1], los, sizes, K)
        lo_t, cnt_t = prep[2], prep[3]
    n = build.capacity
    lo_t, cnt_t = np.asarray(lo_t), np.asarray(cnt_t)
    assert ((lo_t < n) == (cnt_t > 0)).all()
    assert (lo_t[cnt_t == 0] == n).all()
    assert 0 < (cnt_t > 0).sum() < lo_t.shape[0]
    assert cnt_t.max() > 1                        # duplicates are there


# ---------------------------------------------------------------------------
# a build's payload, read where it stands or through sorted copies (PR 36)
# ---------------------------------------------------------------------------

#: the payload zoo's build has 1024 lanes and three payload columns:
#: under 2 * 3 * 1024 positions a program reads it `composed`
_PAYLOAD_BUILD_ROWS = 900
_PAYLOAD_FORMS = {"narrow": ("composed", 120), "wide": ("permuted", 6500)}


def _payload_case(kind, shape):
    """(build, probe, live build rows by key, probe (key, id) rows, the
    keys' bounds): a BIGINT key, then a BIGINT with NULL cells, a two-limb decimal and
    a dictionary column as payload; NULL-key and dead build rows; probe
    keys that miss, NULL probe keys. An expansion's keys stand up to
    four times."""
    import decimal
    rng = np.random.default_rng(36)
    n = _PAYLOAD_BUILD_ROWS
    unique = kind != "expand"
    null_keys, dead_rows = [5, 77, 400], [3, 78, 500, 899]
    keys = (rng.permutation(4 * n)[:n] - n if unique
            else rng.integers(-n // 8, n // 8, n))
    vals = [None if i % 11 == 0 else int(v) for i, v in enumerate(
        rng.integers(-2**52, 2**52, n))]
    decs = [None if i % 13 == 0 else decimal.Decimal(int(v)) * 10**6
            + decimal.Decimal(int(w)) / 100 for i, (v, w) in enumerate(zip(
                rng.integers(-2**52, 2**52, n), rng.integers(0, 10**4, n)))]
    strs = [None if i % 17 == 0 else f"s{int(v)}"
            for i, v in enumerate(rng.integers(0, 9, n))]
    build = _with_nulls(Batch.from_pydict({
        "k": (T.BIGINT, keys.tolist()), "v": (T.BIGINT, vals),
        "dec": (T.decimal(30, 2), decs), "s": (T.VARCHAR, strs)}),
        0, null_keys)
    live = np.ones(build.capacity, dtype=bool)
    live[dead_rows] = False
    build = Batch(build.schema, build.columns,
                  build.row_mask & jnp.asarray(live))
    assert build.capacity == 1024
    by_key = {}
    for i, k in enumerate(keys.tolist()):
        if i not in null_keys + dead_rows:
            by_key.setdefault(k, []).append((vals[i], decs[i], strs[i]))
    if not unique:
        by_key = {k: rows for k, rows in by_key.items() if len(rows) <= 4}
        keep = np.array([k in by_key for k in keys.tolist()])
        build = Batch(build.schema, build.columns,
                      build.row_mask & jnp.asarray(
                          np.pad(keep, (0, build.capacity - n))))
    m = _PAYLOAD_FORMS[shape][1]
    lo, hi = (-n, 3 * n) if unique else (-n // 8, n // 8)
    # two probe keys in three are build keys (NULL-key and dead rows'
    # among them), the rest drawn around the domain
    pk = np.where(rng.random(m) < 0.66, rng.choice(keys, m),
                  rng.integers(lo - 20, hi + 20, m)).tolist()
    probe = _with_nulls(Batch.from_pydict({
        "p": (T.BIGINT, pk), "x": (T.BIGINT, list(range(m)))}), 0, [1, 9])
    prows = [(None if i in (1, 9) else k, i) for i, k in enumerate(pk)]
    return build, probe, by_key, prows, (lo, hi - 1)


def _payload_answer(by_key, prows, join_type):
    out = []
    for k, i in prows:
        hits = by_key.get(k, []) if k is not None else []
        out += [(k, i) + h for h in hits]
        if not hits and join_type == "left":
            out.append((k, i, None, None, None))
    return out


@pytest.mark.parametrize("shape", ["narrow", "wide"])
@pytest.mark.parametrize("kind,join_type,layout", [
    (kind, jt, layout) for kind in ("lookup", "expand")
    for jt in ("inner", "left")
    for layout in ("sorted", "direct", "keyed", "keyed_in_order")
    # a build addressed as it stands holds every key once
    if (kind, layout) != ("expand", "keyed_in_order")])
def test_payload_is_read_the_same_in_both_forms(monkeypatch, kind,
                                                join_type, layout, shape):
    """ISSUE 36: the payload of a build is read `composed` (through the
    permutation at the probe's lanes, from the build as it stands) or
    `permuted` (through sorted copies made at the build's), by the
    static shapes alone: a narrow probe against a wide build and the
    reverse give the rows a plain Python join gives, through
    ``lookup_join`` and ``expand_join`` (k = 4), over every layout."""
    build, probe, by_key, prows, (lo, hi) = _payload_case(kind, shape)
    want_form = _PAYLOAD_FORMS[shape][0]
    if layout == "sorted":
        prep = J.prepare_build(build, [0])
        assert J.lookup_form(prep) == "sorted"
    elif layout == "direct":
        prep = J.prepare_direct(build, [0], lo, hi - lo + 1)
    else:
        los, sizes, K = J.direct_keyed_plan(((lo, hi),))
        prep = J.prepare_direct_keyed(build, [0], los, sizes, K,
                                      unique=layout == "keyed_in_order")
    picked = []
    rule = J.payload_form
    monkeypatch.setattr(J, "payload_form", lambda *a: (
        picked.append((a, rule(*a))) or picked[-1][1]))
    payload, names = [1, 2, 3], ["v", "dec", "s"]
    if kind == "lookup":
        out = J.lookup_join(probe, build, [0], [0], payload, names,
                            join_type, prepared=prep)
        lanes = probe.capacity
    else:
        out = J.expand_join(probe, build, [0], [0], payload, names,
                            join_type, 4, prepared=prep)
        lanes = 4 * probe.capacity
    assert picked == [((lanes, build.capacity, 3), want_form)]
    want = _payload_answer(by_key, prows, join_type)
    assert _rows(out) == _sorted_rows(want)
    assert any(r[2] is None and r[3] is not None for r in want)  # NULL cell
    assert len(prows) // 2 < len(want)
    if join_type == "left":
        assert sum(r[2:] == (None, None, None) for r in want) > 10  # misses


@pytest.mark.parametrize("probe,build,columns,form", [
    (1 << 15, 1 << 20, 4, "composed"),    # TPC-H Q3 against orders
    (1 << 15, 1 << 21, 4, "composed"),
    (1 << 20, 1 << 17, 4, "permuted"),    # equal gather counts: as before
    (1 << 19, 1 << 17, 4, "composed"),
    (1 << 18, 1 << 17, 1, "permuted"),
    (1 << 17, 1 << 17, 1, "composed"),
    (1 << 21, 1 << 9, 8, "permuted"),     # a fact batch against a dimension
    (128, 128, 1, "composed"),
    (128, 1 << 24, 0, "permuted"),        # nothing to read: nothing gathered
])
def test_payload_form_is_decided_by_gather_count(probe, build, columns, form):
    """``composed`` costs one more gather a probe lane, ``permuted`` two
    a build lane a column: the rule, stated once."""
    assert J.payload_form(probe, build, columns) == form
    assert (probe < 2 * columns * build) == (form == "composed")


# ---------------------------------------------------------------------------
# Pallas probe kernel parity (interpret mode on the CPU mesh)
# ---------------------------------------------------------------------------

@pytest.fixture
def no_compare_all(monkeypatch):
    """Every build gets the layout it got before the compare-all form:
    the kernel's tests are about DIRECT tables, and theirs are tiny."""
    monkeypatch.setattr(J, "COMPARE_ALL_LIMIT", 0)


@pytest.fixture
def force_pallas(monkeypatch, no_compare_all):
    monkeypatch.setattr(PJ, "FORCE_PALLAS_PROBE", True)


def test_pallas_lookup_parity_dtypes(force_pallas):
    """Row-exact against the XLA path across the payload dtype zoo:
    64-bit ints, doubles (digit planes), 32-bit ints, bools, dictionary
    strings, decimal128 limb pairs."""
    import decimal
    n = 40
    rng = np.random.default_rng(3)
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, n + 1))),
        "big": (T.BIGINT, rng.integers(-2**52, 2**52, n).tolist()),
        "dbl": (T.DOUBLE, (rng.standard_normal(n) * 1e9).tolist()),
        "i": (T.INTEGER, rng.integers(-100, 100, n).tolist()),
        "b": (T.BOOLEAN, (rng.random(n) < 0.5).tolist()),
        "s": (T.VARCHAR, [f"s{i % 7}" for i in range(n)]),
        "dec": (T.decimal(30, 2),
                [decimal.Decimal(int(v)) * 1000000 +
                 decimal.Decimal(int(w)) / 100
                 for v, w in zip(rng.integers(-2**52, 2**52, n),
                                 rng.integers(0, 10**4, n))]),
    })
    build = _with_nulls(build, 1, [2, 5])
    build = _with_nulls(build, 6, [4])
    probe = Batch.from_pydict({
        "p": (T.BIGINT, rng.integers(-3, n + 4, 64).tolist())})
    prep = J.prepare_direct(build, [0], 1, 64)
    payload = [1, 2, 3, 4, 5, 6]
    names = ["big", "dbl", "i", "b", "s", "dec"]
    for jt in ("inner", "left"):
        a = PJ.lookup_join_direct(probe, build, [0], [0], payload,
                                  names, jt, prep)
        c = J.lookup_join(probe, build, [0], [0], payload, names, jt,
                          prepared=prep)
        assert _rows(a) == _rows(c), jt


def test_pallas_supports_join_gate():
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, 200))),
        "v": (T.BIGINT, list(range(199)))})
    sortp = J.prepare_build(build, [0])
    assert not PJ.supports_join(sortp, build, [1])   # not direct
    prep = J.prepare_direct(build, [0], 1, 256)
    assert not PJ.kernel_enabled()                   # CPU backend
    assert not PJ.supports_join(prep, build, [1])


def test_pallas_supports_join_gate_forced(force_pallas):
    build = Batch.from_pydict({
        "k": (T.BIGINT, list(range(1, 200))),
        "v": (T.BIGINT, list(range(199)))})
    prep = J.prepare_direct(build, [0], 1, 256)
    assert PJ.supports_join(prep, build, [1])
    assert not PJ.supports_join(prep, build, list(range(32)))  # bits


def _star_runner(sf, rows_per_batch):
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=sf))
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=rows_per_batch)


def _tpch_oracle(connector, columns):
    """SQLite over the named columns of the connector's tables (dates
    as ISO text)."""
    import datetime
    import sqlite3
    from presto_tpu.connectors.spi import TableHandle

    def val(v):
        v = v.item() if hasattr(v, "item") else v
        return v.isoformat() if isinstance(v, datetime.date) else v
    conn = sqlite3.connect(":memory:")
    for t, cols in columns.items():
        conn.execute(f"create table {t} ({', '.join(cols)})")
        for split in connector.split_manager.splits(
                TableHandle("tpch", "default", t), 1):
            for b in connector.page_source(split, cols).batches():
                conn.executemany(
                    f"insert into {t} values ({', '.join('?' * len(cols))})",
                    [tuple(val(v) for v in r) for r in b.to_pylist()])
    return conn


def _q3_text():
    """The text the cell ``tpch_sf1_q3`` sends, one binding."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location("bench_q3", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "templates", "q3.py"))
    q3 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(q3)
    return q3.SQL.format(SEGMENT="BUILDING", DATE="1995-03-15")


@pytest.mark.parametrize("case", ["q3_narrowed_probe", "fused_star_chain"])
def test_engine_reads_the_payload_composed_and_answers_as_sqlite(case):
    """ISSUE 36 through the front door. TPC-H Q3 as the cell sends it,
    in two lineitem batches of 2^18 lanes, which the join cuts to its
    matches (2^13 lanes) before it reads the four columns of orders'
    2^16-lane build; and a star chain whose fused tail probes
    customer's 2^11 lanes x 3 columns with 2^13: both count `composed`
    launches and answer as SQLite does."""
    if case == "q3_narrowed_probe":
        sql = _q3_text()
        r = _star_runner(0.05, 1 << 18)
        columns = {
            "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                         "l_shipdate"],
            "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                       "o_shippriority"],
            "customer": ["c_custkey", "c_mktsegment"]}
        moved = "compact_applied_total"
    else:
        sql = ("select o_orderkey, c_name, n_name from orders "
               "join customer on o_custkey = c_custkey "
               "join nation on c_nationkey = n_nationkey "
               "where o_totalprice > 300000 order by o_orderkey")
        r = _star_runner(0.01, 1 << 14)
        columns = {"orders": ["o_orderkey", "o_custkey", "o_totalprice"],
                   "customer": ["c_custkey", "c_name", "c_nationkey"],
                   "nation": ["n_nationkey", "n_name"]}
        moved = "fused_tail_lanes_total"
    oracle = _tpch_oracle(r.session.catalogs.get("tpch"), columns)
    names = ("join_payload_selected_total.composed",
             "join_payload_selected_total.permuted", moved)
    before = [_metric(n) for n in names]
    got = [tuple(v.item() if hasattr(v, "item") else v for v in row)
           for row in r.execute(sql).rows]
    composed, permuted, other = (
        _metric(n) - b for n, b in zip(names, before))
    assert composed >= 1 and other >= 1
    # the other join of each plan reads a build of a few hundred lanes
    # with at least four times as many: as before
    assert permuted >= 1
    want = oracle.execute(sql.replace("date '", "'")).fetchall()
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        g = tuple(v.isoformat() if hasattr(v, "isoformat") else v
                  for v in g)
        assert g == pytest.approx(w, rel=1e-9)


def test_pallas_engine_parity(force_pallas):
    """The 3-way tpch star chain runs the fused pipeline through the
    kernel (interpret mode) with the session property on, and lands on
    the rows of the default XLA probe."""
    r = _star_runner(0.01, 1 << 14)
    q = ("select n_name, count(*) c from orders "
         "join customer on o_custkey = c_custkey "
         "join nation on c_nationkey = n_nationkey "
         "group by n_name order by n_name")
    before = _metric("join_strategy_selected_total.direct.replicated")
    on = r.execute(q, properties={"join_pallas_probe": True}).rows
    after = _metric("join_strategy_selected_total.direct.replicated")
    assert after > before
    off = r.execute(q).rows
    assert on == off


def test_pallas_probe_off_by_default():
    """The kernel does not lower for a TPU v5e (ops/pallas_join
    docstring, tests/test_tpu_compile.py): the property defaults off in
    the registry AND at the executor's read, even on a backend that
    reports the kernel enabled."""
    from presto_tpu.config import SESSION_PROPERTIES
    assert SESSION_PROPERTIES["join_pallas_probe"].default is False
    from presto_tpu.exec.local import _Executor
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.planner.planner import Session
    cats = CatalogManager()
    assert not _Executor(Session(cats), 1 << 13)._pallas_probe_on()
    on = Session(cats, properties={"join_pallas_probe": True})
    assert _Executor(on, 1 << 13)._pallas_probe_on()


@pytest.mark.parametrize("fused", [False, True])
def test_pallas_kernel_failure_fails_query(monkeypatch, no_compare_all,
                                           fused):
    """With join_pallas_probe=true a kernel that fails to lower fails
    the query with the compiler's message — nothing re-runs it on
    another implementation. With the default (off) the same query
    never reaches the kernel."""
    monkeypatch.setattr(PJ, "kernel_enabled", lambda: True)

    def boom(*a, **k):
        raise NotImplementedError("Only 2D gather is supported")
    monkeypatch.setattr(PJ, "lookup_join_direct", boom)
    monkeypatch.setattr(
        "presto_tpu.exec.local.lookup_join_pallas_jit", boom)
    r = _star_runner(0.002, 1 << 13)
    q = ("select count(*) from orders join customer "
         "on o_custkey = c_custkey where c_nationkey = 3")
    props = {"fused_pipeline": fused}
    assert r.execute(q, properties=props).rows[0][0] > 0
    with pytest.raises(Exception, match="Only 2D gather"):
        r.execute(q, properties={**props, "join_pallas_probe": True})


# ---------------------------------------------------------------------------
# planner: strategy attaches from stats, flips when stats change
# ---------------------------------------------------------------------------

def _find(node, cls):
    from presto_tpu.planner.plan import PlanNode
    out = []

    def walk(n):
        if isinstance(n, cls):
            out.append(n)
        for c in n.children:
            walk(c)
    walk(node)
    return out


@pytest.fixture(scope="module")
def tpch_runner():
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=0.01))
    return LocalRunner(catalogs=catalogs, catalog="tpch",
                       rows_per_batch=1 << 14)


def test_planner_attaches_join_bounds(tpch_runner):
    from presto_tpu.planner.plan import JoinNode
    plan = tpch_runner.plan(
        "select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey")
    joins = _find(plan.root, JoinNode)
    assert joins and joins[0].key_bounds == ((0, 24),)
    ex = tpch_runner.execute(
        "explain select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey").rows
    text = "\n".join(r[0] for r in ex)
    assert "direct bounds=[0..24]" in text


def test_planner_bounds_flip_with_stats(tpch_runner, monkeypatch):
    """Same SQL, stats withdrawn -> the strategy flips to sorted (no
    key_bounds); join_dense_path=false pins the old behavior too."""
    from presto_tpu.connectors.spi import TableStats
    from presto_tpu.planner.plan import JoinNode
    sql = ("select c_name, n_name from customer "
           "join nation on c_nationkey = n_nationkey")
    conn = tpch_runner.session.catalogs.get("tpch")
    meta = conn.metadata
    real = meta.table_stats

    def no_bounds(table):
        st = real(table)
        if table.table == "nation":
            return TableStats(row_count=st.row_count, columns={},
                              primary_key=st.primary_key)
        return st
    monkeypatch.setattr(type(meta), "table_stats",
                        lambda self, t: no_bounds(t))
    try:
        plan = tpch_runner.plan(sql)
    finally:
        monkeypatch.undo()
    joins = _find(plan.root, JoinNode)
    assert joins and joins[0].key_bounds == ()
    # session escape hatch
    old = dict(tpch_runner.session.properties)
    tpch_runner.session.properties["join_dense_path"] = False
    try:
        plan2 = tpch_runner.plan(sql)
    finally:
        tpch_runner.session.properties.clear()
        tpch_runner.session.properties.update(old)
    assert _find(plan2.root, JoinNode)[0].key_bounds == ()


def test_semi_distribution_from_stats(tpch_runner):
    """Semi joins stop broadcasting membership everywhere: a filtering
    set estimated above broadcast_join_row_limit partitions; NULL-aware
    anti joins always replicate (global NULL semantics)."""
    from presto_tpu.planner.plan import SemiJoinNode
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer)")
    plan = tpch_runner.plan(sql)
    semis = _find(plan.root, SemiJoinNode)
    assert semis and semis[0].distribution == "replicated"
    old = dict(tpch_runner.session.properties)
    tpch_runner.session.properties["broadcast_join_row_limit"] = 100
    try:
        plan2 = tpch_runner.plan(sql)
        semis2 = _find(plan2.root, SemiJoinNode)
        assert semis2 and semis2[0].distribution == "partitioned"
        anti = tpch_runner.plan(
            "select count(*) from orders where o_custkey not in "
            "(select c_custkey from customer)")
        asemis = _find(anti.root, SemiJoinNode)
        assert asemis and asemis[0].negated
        assert asemis[0].distribution == "replicated"
    finally:
        tpch_runner.session.properties.clear()
        tpch_runner.session.properties.update(old)


def test_semi_partitioned_row_parity(tpch_runner):
    """Forcing the partitioned semi distribution returns the identical
    rows (the fragmenter/mesh path composes per-partition verdicts)."""
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer where c_nationkey < 5)")
    a = tpch_runner.execute(sql).rows
    b = tpch_runner.execute(
        sql, properties={"broadcast_join_row_limit": 10}).rows
    assert a == b


# ---------------------------------------------------------------------------
# bounds that lie -> STATS_BOUND_VIOLATION through the error channel
# ---------------------------------------------------------------------------

def _lying_dim():
    """(runner, connector, stats) over fact(fk, v) and a 3-key dim(k,
    name) whose promised bounds (1..5) its key 99 breaks."""
    from presto_tpu.connectors.spi import (CatalogManager, ColumnStats,
                                           TableStats)
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.exec.runner import LocalRunner
    conn = MemoryConnector()
    catalogs = CatalogManager()
    catalogs.register("memory", conn)
    r = LocalRunner(catalogs=catalogs, catalog="memory")
    r.execute("create table memory.default.dim as select * from "
              "(values (1, 'a'), (2, 'b'), (99, 'z')) t(k, name)")
    r.execute("create table memory.default.fact as select * from "
              "(values (1, 10), (2, 20), (99, 30)) t(fk, v)")
    lying = {
        "dim": TableStats(
            row_count=3.0,
            columns={"k": ColumnStats(3, 0.0, 1, 5)},  # 99 violates
            primary_key=("k",)),
        "fact": TableStats(row_count=3.0, columns={}),
    }
    return r, conn, lambda self, t: lying.get(
        t.table, TableStats(row_count=3.0))


def test_join_bound_violation_fails_query():
    from presto_tpu.errors import QueryError
    r, conn, monkeypatch_stats = _lying_dim()
    meta = conn.metadata
    orig = type(meta).table_stats
    type(meta).table_stats = monkeypatch_stats
    try:
        plan = r.plan("select v, name from memory.default.fact "
                      "join memory.default.dim on fk = k")
        from presto_tpu.planner.plan import JoinNode
        joins = _find(plan.root, JoinNode)
        assert joins and joins[0].key_bounds == ((1, 5),)
        with pytest.raises(QueryError) as ei:
            r.execute("select v, name from memory.default.fact "
                      "join memory.default.dim on fk = k")
        assert ei.value.name == "STATS_BOUND_VIOLATION"
        # honest bounds: same query with the real (empty) stats runs.
        # plan_cache=false: the cached plan still carries the lying
        # bounds (stats changes don't bump connector data versions)
        type(meta).table_stats = orig
        rows = r.execute("select v, name from memory.default.fact "
                         "join memory.default.dim on fk = k",
                         properties={"plan_cache": False}).rows
        assert sorted(rows) == [(10, 'a'), (20, 'b'), (30, 'z')]
    finally:
        type(meta).table_stats = orig


# ---------------------------------------------------------------------------
# observability: EXPLAIN ANALYZE strategy rows
# ---------------------------------------------------------------------------

def test_explain_analyze_shows_strategy(tpch_runner):
    ex = tpch_runner.execute(
        "explain analyze select c_name, n_name from customer "
        "join nation on c_nationkey = n_nationkey").rows
    text = "\n".join(r[0] for r in ex)
    # nation is 25 keys: no table is filled for it, no lane gathers
    assert "[strategy compare/replicated]" in text


def test_small_build_is_compared_not_gathered(tpch_runner, monkeypatch):
    """ISSUE 34: a join (and a semi join) whose build is under
    COMPARE_ALL_LIMIT fills no table although the planner promised
    bounds: strategy ``compare``, the rows the ``direct`` strategy
    returns, and a promise that lies still fails the query."""
    sql = ("select c_name, n_name from customer join nation "
           "on c_nationkey = n_nationkey where c_custkey in "
           "(select o_custkey from orders where o_totalprice > 495000)")
    name = "join_strategy_selected_total.%s.replicated"
    c0, d0 = _metric(name % "compare"), _metric(name % "direct")
    text = "\n".join(r[0] for r in tpch_runner.execute(
        "explain analyze " + sql).rows)
    assert text.count("[strategy compare/replicated]") == 2
    rows = tpch_runner.execute(sql).rows
    assert rows and _metric(name % "compare") == c0 + 4
    assert _metric(name % "direct") == d0
    with monkeypatch.context() as m:
        m.setattr(J, "COMPARE_ALL_LIMIT", 0)
        direct = tpch_runner.execute(sql).rows
    assert _metric(name % "direct") == d0 + 2
    assert sorted(rows) == sorted(direct)
    # lying bounds over a 3-key build (test_join_bound_violation_fails_
    # query's tables): compared, and refused all the same
    from presto_tpu.errors import QueryError
    r, conn, lying_stats = _lying_dim()
    monkeypatch.setattr(type(conn.metadata), "table_stats", lying_stats)
    c1 = _metric(name % "compare")
    with pytest.raises(QueryError) as ei:
        r.execute("select v, name from memory.default.fact "
                  "join memory.default.dim on fk = k")
    assert ei.value.name == "STATS_BOUND_VIOLATION"
    assert _metric(name % "compare") == c1 + 1
