"""The data of the ``tpch_sf10_custorders`` deployment, by the
benchmark's own definition: ``tpchdata``'s tables as they are, and the
two columns TPC-H Q18 reads beside them, ``o_totalprice`` and
``c_name``, stated here a second time in plain NumPy (the first is
``presto_tpu.connectors.tpch``, of which nothing is imported).
``benchmarks/tests/test_q18_cell.py`` holds the two against each other at
SF0.01.
"""
from __future__ import annotations

import numpy as np

import tpchdata
from tpchdata import lineitem_rows, map_lineitem, row_counts  # noqa: F401

#: ``o_totalprice``: whole cents drawn uniformly from 1000.00..500000.00
#: by the order key's hash (stream 3), then ONE division
TOTALPRICE_CENTS = (100_000, 50_000_000)


def totalprice_cents(key: np.ndarray) -> np.ndarray:
    lo, hi = TOTALPRICE_CENTS
    return lo + tpchdata._mod(key, 3, hi - lo + 1)


def orders(sf: float, lo: int, hi: int) -> dict:
    """``tpchdata.orders`` with ``o_totalprice_cents`` (exact) and
    ``o_totalprice`` (the DOUBLE the schema implies)."""
    out = tpchdata.orders(sf, lo, hi)
    cents = totalprice_cents(out["o_orderkey"])
    return dict(out, o_totalprice_cents=cents,
                o_totalprice=cents.astype(np.float64) / 100.0)


def customer_name(custkey) -> str:
    return "Customer#%09d" % int(custkey)


def customer(sf: float, lo: int, hi: int) -> dict:
    """``tpchdata.customer`` with ``c_name``, one value a row."""
    out = tpchdata.customer(sf, lo, hi)
    return dict(out, c_name=np.array(
        [customer_name(k) for k in out["c_custkey"]], dtype=object))
