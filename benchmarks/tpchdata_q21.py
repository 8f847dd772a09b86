"""The data of the ``tpch_sf10_suppwait`` deployment, by the benchmark's
own definition: what TPC-H Q21 reads beyond ``tpchdata``, stated here a
second time in plain NumPy (the first is ``presto_tpu.connectors.tpch``,
of which nothing is imported): lineitem's ``l_suppkey`` (through the
part key and the specification's supplier-of-part formula),
``l_commitdate`` and ``l_receiptdate``, orders' ``o_orderstatus``,
supplier (``s_suppkey``, ``s_name``, ``s_nationkey``) and nation
(``n_nationkey``, ``n_name``). ``benchmarks/tests/test_q21_cell.py``
holds the two against each other at SF0.01.
"""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

import tpchdata
from tpchdata import (CURRENT_DATE, ORDERDATE_SPAN, START_DATE,  # noqa: F401
                      _mod, lineitem_rows, workers)

ORDER_STATUS = ("F", "O", "P")

#: ``n_name`` by ``n_nationkey`` (0..24), the specification's clause
#: 4.2.3 list in the generator's order
NATIONS = (
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES")

#: an order has 1 to 7 lines (``1 + hash % 7``)
MAX_LINES = 7


def row_counts(sf: float) -> dict:
    return dict(tpchdata.row_counts(sf), supplier=int(10_000 * sf),
                nation=len(NATIONS))


def orders(sf: float, lo: int, hi: int) -> dict:
    """``tpchdata.orders`` with ``o_orderstatus`` as a code into
    ``ORDER_STATUS``: F where the order is older than half a year at the
    current date (all lines shipped), O where it is later than the
    current date, P between."""
    out = tpchdata.orders(sf, lo, hi)
    odate = out["o_orderdate"]
    return dict(out, o_orderstatus=np.where(
        odate + 182 < CURRENT_DATE, 0, np.where(odate > CURRENT_DATE, 1, 2)))


def supplier_of_part(partkey: np.ndarray, i: np.ndarray,
                     suppliers: int) -> np.ndarray:
    """The specification's clause 4.2.3 formula for the ``i``-th (0..3)
    supplier of a part."""
    s = suppliers
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def lineitem(sf: float, lo: int, hi: int) -> dict:
    """The columns Q21 reads of every line of the orders ``lo..hi-1``,
    an order's lines adjacent and orders ascending; ``lines`` is the
    number of lines of each ORDER of the span."""
    okey = np.arange(lo, hi, dtype=np.int64)
    counts = 1 + _mod(okey, 100, MAX_LINES)
    rep = np.repeat(okey, counts)
    first = np.cumsum(counts) - counts
    ln = np.arange(len(rep), dtype=np.int64) - np.repeat(first, counts)
    key = rep * 8 + ln
    odate = np.repeat(START_DATE + _mod(okey, 5, ORDERDATE_SPAN), counts)
    partkey = 1 + _mod(key, 11, row_counts(sf)["part"])
    shipdate = odate + 1 + _mod(key, 17, 121)
    return {
        "l_orderkey": rep,
        "l_suppkey": supplier_of_part(partkey, _mod(key, 12, 4),
                                      row_counts(sf)["supplier"]),
        "l_commitdate": odate + 30 + _mod(key, 18, 61),
        "l_receiptdate": shipdate + 1 + _mod(key, 19, 30),
        "lines": counts,
    }


def map_lineitem(fn, sf: float, orders_per_chunk: int = 1 << 18) -> list:
    """``fn(chunk, lo, hi)`` over this file's lineitem in chunks of whole
    orders ``lo..hi-1``, on a thread pool; the results in order of the
    order keys."""
    n = row_counts(sf)["orders"]
    spans = [(a, min(a + orders_per_chunk, n + 1))
             for a in range(1, n + 1, orders_per_chunk)]
    with cf.ThreadPoolExecutor(workers()) as pool:
        return list(pool.map(lambda s: fn(lineitem(sf, *s), *s), spans))


def supplier_name(suppkey) -> str:
    return "Supplier#%09d" % int(suppkey)


def supplier(sf: float, lo: int, hi: int) -> dict:
    key = np.arange(lo, hi, dtype=np.int64)
    return {"s_suppkey": key, "s_nationkey": _mod(key, 51, len(NATIONS)),
            "s_name": np.array([supplier_name(k) for k in key],
                               dtype=object)}


def nation() -> dict:
    return {"n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
            "n_name": np.array(NATIONS, dtype=object)}
