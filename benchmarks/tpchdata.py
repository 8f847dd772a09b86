"""The deployment's data, by the benchmark's own definition.

The configurations (``configs/tpch_sf*.json``) state that the tables
are TPC-H as ``presto_tpu.connectors.tpch`` defines it: every column a
stateless splitmix64 hash of the row's key (NOT dbgen-compatible,
uniform keys). This file is that definition written down a second time,
in plain NumPy, for the columns the templates read, so that the
reference makes its own data from the scale factor alone and takes
nothing from the program. Where the program's generator drifts from it,
the answers differ and ``correct`` is false — which is the point: the
tables are part of the deployment. ``tests/test_reference.py`` holds the
two generators against each other at SF0.01.

Money and percentages are generated as integers (cents, hundredths) and
turned into DOUBLE by the same single division the schema implies, so a
template can compare a decimal literal exactly.
"""
from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

START_DATE = 8035        # 1992-01-01 as an epoch day
END_ORDERDATE = 10440    # 1998-08-02
CURRENT_DATE = 9298      # 1995-06-17
ORDERDATE_SPAN = END_ORDERDATE - START_DATE + 1

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
RETURN_FLAGS = ("A", "N", "R")
LINE_STATUS = ("O", "F")

_U64 = np.uint64
_MASK = 0xFFFFFFFFFFFFFFFF


def _hash(key: np.ndarray, tag: int) -> np.ndarray:
    """splitmix64 of ``key ^ (tag * golden)``: one stream per column."""
    with np.errstate(over="ignore"):
        x = key.astype(_U64) ^ _U64((tag * 0x9E3779B97F4A7C15) & _MASK)
        x = x + _U64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
        return x ^ (x >> _U64(31))


def _mod(key, tag, n) -> np.ndarray:
    return (_hash(key, tag) % _U64(n)).astype(np.int64)


def row_counts(sf: float) -> dict:
    """Rows of customer, orders and part at ``sf`` (lineitem's count
    follows from the orders: see :func:`lineitem_rows`)."""
    return {"customer": int(150_000 * sf), "orders": int(1_500_000 * sf),
            "part": int(200_000 * sf)}


def orders(sf: float, lo: int, hi: int) -> dict:
    """Orders with keys ``lo..hi-1`` (keys run from 1)."""
    key = np.arange(lo, hi, dtype=np.int64)
    n_cust = row_counts(sf)["customer"]
    ck = 1 + _mod(key, 1, n_cust)
    return {
        "o_orderkey": key,
        # a third of the customers never place an order
        "o_custkey": np.where(ck % 3 == 0, np.maximum(ck - 1, 1), ck),
        "o_orderdate": START_DATE + _mod(key, 5, ORDERDATE_SPAN),
        "o_shippriority": np.zeros(len(key), dtype=np.int64),
    }


def customer(sf: float, lo: int, hi: int) -> dict:
    key = np.arange(lo, hi, dtype=np.int64)
    return {"c_custkey": key, "c_mktsegment": _mod(key, 35, len(SEGMENTS))}


def lineitem(sf: float, lo: int, hi: int) -> dict:
    """Every line of the orders ``lo..hi-1``, an order's lines adjacent
    and orders ascending. Besides the DOUBLE columns it gives
    ``l_discount_pct``, ``l_tax_pct`` (hundredths) and ``l_quantity_int``
    for exact comparisons with decimal literals."""
    okey = np.arange(lo, hi, dtype=np.int64)
    counts = 1 + _mod(okey, 100, 7)
    rep = np.repeat(okey, counts)
    first = np.cumsum(counts) - counts
    ln = np.arange(len(rep), dtype=np.int64) - np.repeat(first, counts)
    key = rep * 8 + ln
    odate = np.repeat(START_DATE + _mod(okey, 5, ORDERDATE_SPAN), counts)
    partkey = 1 + _mod(key, 11, row_counts(sf)["part"])
    quantity = 1 + _mod(key, 13, 50)
    shipdate = odate + 1 + _mod(key, 17, 121)
    receipt = shipdate + 1 + _mod(key, 19, 30)
    disc = _mod(key, 14, 11)
    tax = _mod(key, 15, 9)
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) \
        / 100.0
    return {
        "l_orderkey": rep,
        "l_quantity_int": quantity,
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": quantity * retail,
        "l_discount_pct": disc,
        "l_discount": disc.astype(np.float64) / 100.0,
        "l_tax_pct": tax,
        "l_tax": tax.astype(np.float64) / 100.0,
        "l_returnflag": np.where(receipt <= CURRENT_DATE,
                                 _mod(key, 16, 2) * 2, 1),
        "l_linestatus": np.where(shipdate > CURRENT_DATE, 0, 1),
        "l_shipdate": shipdate,
    }


def lineitem_rows(sf: float) -> int:
    """Lines the whole table holds (59,987,676 at SF10)."""
    n = row_counts(sf)["orders"]
    return sum(int((1 + _mod(np.arange(a, min(a + (1 << 22), n + 1),
                                       dtype=np.int64), 100, 7)).sum())
               for a in range(1, n + 1, 1 << 22))


def workers() -> int:
    """Threads for a pass over a table: NumPy releases the interpreter
    lock in its loops. Most of the host's cores, never all."""
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def map_lineitem(fn, sf: float, orders_per_chunk: int = 1 << 18) -> list:
    """``fn(chunk)`` over lineitem in chunks of whole orders, on a
    thread pool; the results in order of the order keys."""
    n = row_counts(sf)["orders"]
    spans = [(a, min(a + orders_per_chunk, n + 1))
             for a in range(1, n + 1, orders_per_chunk)]
    with cf.ThreadPoolExecutor(workers()) as pool:
        return list(pool.map(lambda s: fn(lineitem(sf, *s)), spans))
