"""TPC-H Q3, the shipping-priority query: customer x orders x lineitem,
a group-by on the order (millions of groups), top ten by revenue.

The SQL text is the benchmark's own (Presto's
``sql/presto/tpch/q03.sql`` with named holes). ``reference`` is plain
NumPy over the benchmark's own data and shares nothing with the program.
"""
import datetime

import numpy as np

SQL = """\
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = '{SEGMENT}' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '{DATE}' and l_shipdate > date '{DATE}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate, l_orderkey
limit 10"""

#: TPC-H specification clause 2.4.3.3, written from memory
ASSUMED = {
    "SEGMENT": "one of the five market segments",
    "DATE": "a day drawn from 1995-03-01..1995-03-31",
}

KINDS = ("int", "double", "date", "int")

#: between the lower reading 4.96e-11 (the program on the chip, SF1, a
#: dozen seeds: its sort-path sums are differences of a running f64 sum
#: over a 2^20-row batch) and the upper 7.12e-8 (the float32 control,
#: the same seeds), the more room above the lower (my chip run, PR 25)
DOUBLE_REL_LIMIT = 3e-9

SCAN_COLUMNS = {
    "lineitem": {"l_orderkey": 8, "l_extendedprice": 8, "l_discount": 8,
                 "l_shipdate": 4},
    "orders": {"o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4,
               "o_shippriority": 4},
    "customer": {"c_custkey": 8, "c_mktsegment": 4},
}

LIMIT = 10

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")


def draw(rng) -> dict:
    return {"SEGMENT": rng.choice(SEGMENTS),
            "DATE": f"1995-03-{rng.randint(1, 31):02d}"}


def _top(okey, rev, odate):
    order = np.lexsort((okey, odate, -rev))[:LIMIT]
    return okey[order], rev[order], odate[order]


def reference(data, sf: float, bindings, float_type=np.float64) -> list:
    ft = float_type
    counts = data.row_counts(sf)
    cu = data.customer(sf, 1, counts["customer"] + 1)
    od = data.orders(sf, 1, counts["orders"] + 1)
    # orders' keys run 1..n: position = key - 1
    odate_of = od["o_orderdate"]
    builds = []
    for b in bindings:
        day = (datetime.date.fromisoformat(b["DATE"])
               - datetime.date(1970, 1, 1)).days
        seg = data.SEGMENTS.index(b["SEGMENT"])
        cust_ok = np.zeros(counts["customer"] + 1, dtype=bool)
        cust_ok[cu["c_custkey"][cu["c_mktsegment"] == seg]] = True
        order_ok = cust_ok[od["o_custkey"]] & (odate_of < day)
        builds.append((day, order_ok))

    def part(li):
        okey = li["l_orderkey"]
        first = np.flatnonzero(np.diff(okey, prepend=okey[0] - 1))
        keys = okey[first]
        val = li["l_extendedprice"].astype(ft) \
            * (ft(1) - li["l_discount"].astype(ft))
        out = []
        for day, order_ok in builds:
            m = (li["l_shipdate"] > day) & order_ok[okey - 1]
            rev = np.add.reduceat(np.where(m, val, ft(0)), first, dtype=ft)
            hit = np.add.reduceat(m.astype(np.int64), first) > 0
            out.append(_top(keys[hit], rev[hit], odate_of[keys[hit] - 1]))
        return out

    parts = data.map_lineitem(part, sf)
    answers = []
    for i in range(len(bindings)):
        okey, rev, odate = _top(*(np.concatenate([p[i][j] for p in parts])
                                  for j in range(3)))
        answers.append([(int(k), float(r), int(d), 0)
                        for k, r, d in zip(okey, rev, odate)])
    return answers
