"""TPC-H Q6, the forecasting-revenue-change query: one scan of lineitem,
three DOUBLE compares, a multiply and a global sum.

The SQL text is the benchmark's own (Presto's
``sql/presto/tpch/q06.sql`` with named holes). ``reference`` is plain
NumPy over the benchmark's own data (``tpchdata``) and shares nothing
with the program.
"""
import datetime
import decimal

import numpy as np

SQL = """\
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '{DATE}'
  and l_shipdate < date '{DATE}' + interval '1' year
  and l_discount between {DISCOUNT} - 0.01 and {DISCOUNT} + 0.01
  and l_quantity < {QUANTITY}"""

#: substitution parameters, TPC-H specification clause 2.4.6.3, written
#: from memory (no network here): each range is an assumption
ASSUMED = {
    "DATE": "the first of January of a year drawn from 1993..1997",
    "DISCOUNT": "drawn from 0.02..0.09 in steps of 0.01",
    "QUANTITY": "24 or 25",
}

#: kind of each answer column: DOUBLE cells compare by relative gap,
#: every other kind exactly
KINDS = ("double",)

#: the widest relative gap a DOUBLE cell of the answer may show against
#: the reference. Provisional: the template is in no cell yet (PERF.md,
#: Open questions). Sound answers on the chip at SF1 read up to 1.4e-15
#: (three bindings), the float32 control 3.1e-8 at SF0.1 on the host;
#: read both again at SF10 on a dozen seeds when the cell is added
DOUBLE_REL_LIMIT = 1e-11

#: bytes a row of each scanned column takes on the device
SCAN_COLUMNS = {"lineitem": {"l_shipdate": 4, "l_discount": 8,
                             "l_quantity": 8, "l_extendedprice": 8}}


def draw(rng) -> dict:
    return {"DATE": f"{rng.randint(1993, 1997)}-01-01",
            "DISCOUNT": f"0.0{rng.randint(2, 9)}",
            "QUANTITY": str(rng.randint(24, 25))}


def _day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso)
            - datetime.date(1970, 1, 1)).days


def reference(data, sf: float, bindings, float_type=np.float64) -> list:
    """One answer (a list of rows) per binding, from one pass over
    lineitem. ``float_type`` is the arithmetic of every DOUBLE
    expression and sum: float64 as the configuration states, float32
    for the control."""
    ft = float_type
    preds = []
    for b in bindings:
        year = int(b["DATE"][:4])
        # `D - 0.01` is decimal arithmetic in SQL: 0.06 - 0.01 is 0.05
        # exactly, so the bounds are formed as decimals, in hundredths
        d = decimal.Decimal(b["DISCOUNT"])
        preds.append((_day(b["DATE"]), _day(f"{year + 1}{b['DATE'][4:]}"),
                      int((d - decimal.Decimal("0.01")) * 100),
                      int((d + decimal.Decimal("0.01")) * 100),
                      int(b["QUANTITY"])))

    def part(li):
        prod = li["l_extendedprice"].astype(ft) * li["l_discount"].astype(ft)
        out = []
        for lo, hi, dlo, dhi, qty in preds:
            m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
                 & (li["l_discount_pct"] >= dlo)
                 & (li["l_discount_pct"] <= dhi)
                 & (li["l_quantity_int"] < qty))
            out.append((prod[m].sum(dtype=ft), int(m.sum())))
        return out

    parts = data.map_lineitem(part, sf)
    answers = []
    for i in range(len(bindings)):
        total, n = ft(0.0), 0
        for p in parts:
            total = ft(total + p[i][0])
            n += p[i][1]
        answers.append([(float(total) if n else None,)])
    return answers
