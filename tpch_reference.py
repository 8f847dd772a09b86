"""Plain NumPy implementations of TPC-H Q6, Q1, Q3 and Q18 over the chunks
``TpchConnector`` generates — the reference ``chip_smoke.py`` calls
(as the answer the chip must reproduce). They share no code with the
engine beyond the generator that makes the data: no planner, no expression compiler, no
JAX. One chunk is a tuple of host column arrays in the order the
function documents, followed by a bool row mask.
"""
from __future__ import annotations

import datetime

import numpy as np


def _epoch_day(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


D_Q1 = _epoch_day(1998, 9, 2)    # 1998-12-01 - 90 days
D_Q3 = _epoch_day(1995, 3, 15)
D_Q6_LO = _epoch_day(1994, 1, 1)
D_Q6_HI = _epoch_day(1995, 1, 1)

Q6_COLS = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
Q1_COLS = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax", "l_shipdate"]
Q3_LINEITEM_COLS = ["l_orderkey", "l_extendedprice", "l_discount",
                    "l_shipdate"]
Q3_ORDERS_COLS = ["o_orderkey", "o_custkey", "o_orderdate",
                  "o_shippriority"]
Q3_CUSTOMER_COLS = ["c_custkey", "c_mktsegment"]
Q18_LINEITEM_COLS = ["l_orderkey", "l_quantity"]
Q18_ORDERS_COLS = ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"]
Q18_CUSTOMER_COLS = ["c_custkey", "c_name"]


def stage_host(conn, table, cols, rows_per_batch=1 << 20):
    """Generate one table's columns host-side, chunk by chunk:
    ``(chunks, n_rows, schema, vocabs)`` with one ``(col arrays...,
    mask)`` tuple per chunk and the dictionary vocabulary (or None) per
    column."""
    from presto_tpu.connectors.spi import TableHandle

    th = TableHandle("tpch", "t", table)
    split = conn.split_manager.splits(th, 1)[0]
    host, n = [], 0
    schema = None
    vocabs = None
    ps = conn.page_source(split, cols, rows_per_batch=rows_per_batch)
    for chunk_schema, data, cn in ps.host_chunks():
        schema = chunk_schema.select(list(cols))
        arrays = []
        vocabs = []
        for name in cols:
            arr, vocab = data[name]
            if isinstance(vocab, str):     # free text: the strings as they are
                arrays.append(np.asarray(arr, dtype=object))
                vocabs.append(None)
                continue
            arrays.append(np.asarray(arr))
            vocabs.append(tuple(vocab) if vocab is not None else None)
        host.append(tuple(arrays) + (np.ones(cn, dtype=bool),))
        n += cn
    return host, n, schema, vocabs


def select_cols(host, have, want):
    """Chunks re-ordered to ``want`` out of chunks staged as ``have``
    (array references, no copy) — one generation pass serves every
    query's column order."""
    idx = [have.index(c) for c in want]
    return [tuple(h[i] for i in idx) + (h[-1],) for h in host]


def q6_numpy(host) -> float:
    """Chunks in ``Q6_COLS`` order -> revenue."""
    acc = 0.0
    for ship, disc, qty, price, mask in host:
        # price/discount/quantity are 2-decimal quantities; np.round
        # pins them to the literal the predicate compares against
        disc2, qty2, price2 = (np.round(c, 2) for c in (disc, qty, price))
        m = (mask & (ship >= D_Q6_LO) & (ship < D_Q6_HI)
             & (disc2 >= 0.05) & (disc2 <= 0.07) & (qty2 < 24.0))
        acc += float(np.sum(np.where(m, price2 * disc2, 0.0)))
    return acc


def q1_numpy_sums(host, n_rf: int, n_ls: int) -> dict:
    """Chunks in ``Q1_COLS`` order -> {(rf code, ls code): [sum_qty,
    sum_base, sum_disc_price, sum_charge, sum_disc, count]}."""
    sums = {}
    for (rf, ls, qty, price, disc, tax, ship, mask) in host:
        m = mask & (ship <= D_Q1)
        qty2, price2, disc2, tax2 = (np.round(c, 2)
                                     for c in (qty, price, disc, tax))
        for code_rf in range(n_rf):
            for code_ls in range(n_ls):
                g = m & (rf == code_rf) & (ls == code_ls)
                if not g.any():
                    continue
                dp = price2[g] * (1.0 - disc2[g])
                ch = dp * (1.0 + tax2[g])
                acc = sums.setdefault((code_rf, code_ls), np.zeros(6))
                acc += [qty2[g].sum(), price2[g].sum(), dp.sum(),
                        ch.sum(), disc2[g].sum(), g.sum()]
    return sums


def q1_numpy_rows(host, rf_vocab, ls_vocab) -> list:
    """The Q1 result rows in SQL column order, sorted by (returnflag,
    linestatus)."""
    rows = []
    for (crf, cls_), a in q1_numpy_sums(host, len(rf_vocab),
                                        len(ls_vocab)).items():
        n = a[5]
        rows.append((rf_vocab[crf], ls_vocab[cls_], a[0], a[1], a[2],
                     a[3], a[0] / n, a[1] / n, a[4] / n, int(n)))
    rows.sort(key=lambda r: (r[0], r[1]))
    return rows


def q3_numpy(c_host, o_host, li_host, seg_code: int, limit: int = 10):
    """Chunks in ``Q3_*_COLS`` orders -> top ``limit`` of (l_orderkey,
    revenue, o_orderdate epoch day, o_shippriority) by revenue desc,
    o_orderdate."""
    ck, cseg, cmask = tuple(
        np.concatenate([h[i] for h in c_host]) for i in range(3))
    ok_, ocust, odate, oprio, omask = tuple(
        np.concatenate([h[i] for h in o_host]) for i in range(5))
    cust_keys = np.sort(ck[cmask & (cseg == seg_code)])
    om = omask & (odate < D_Q3)
    if len(cust_keys):
        pos = np.minimum(np.searchsorted(cust_keys, ocust),
                         len(cust_keys) - 1)
        om &= cust_keys[pos] == ocust
    else:
        om &= False
    bk = ok_[om]
    order_sort = np.argsort(bk, kind="stable")
    bkey = bk[order_sort]
    bdate = odate[om][order_sort]
    bprio = oprio[om][order_sort]
    rev_acc = np.zeros(len(bkey))
    for (lk, price, disc, ship, mask) in li_host:
        m = mask & (ship > D_Q3)
        price2 = np.round(price, 2)
        disc2 = np.round(disc, 2)
        if not len(bkey):
            continue
        p = np.minimum(np.searchsorted(bkey, lk), len(bkey) - 1)
        hit = m & (bkey[p] == lk)
        np.add.at(rev_acc, p[hit], price2[hit] * (1.0 - disc2[hit]))
    nz = rev_acc > 0
    order = np.lexsort((bdate[nz], -rev_acc[nz]))[:limit]
    return [(int(k), float(r), int(d), int(pr))
            for k, r, d, pr in zip(bkey[nz][order], rev_acc[nz][order],
                                   bdate[nz][order], bprio[nz][order])]


def q18_numpy(c_host, o_host, li_host, quantity: float, limit: int = 100):
    """Chunks in ``Q18_*_COLS`` orders -> the first ``limit`` of (c_name,
    c_custkey, o_orderkey, o_orderdate epoch day, o_totalprice,
    sum(l_quantity)) by o_totalprice desc, o_orderdate, o_orderkey, over
    the orders whose quantities add up to MORE than ``quantity``."""
    ck, cname, cmask = tuple(
        np.concatenate([h[i] for h in c_host]) for i in range(3))
    ok_, ocust, oprice, odate, omask = tuple(
        np.concatenate([h[i] for h in o_host]) for i in range(5))
    lk, lqty, lmask = tuple(
        np.concatenate([h[i] for h in li_host]) for i in range(3))
    # a bincount of quantities per order (quantities are whole numbers:
    # the sums are exact), then the threshold, strictly
    total = np.bincount(lk[lmask], weights=np.round(lqty[lmask], 2),
                        minlength=int(ok_.max()) + 1)
    om = omask & (total[ok_] > quantity)
    okey, cust, price, date = ok_[om], ocust[om], oprice[om], odate[om]
    # the three lookups: the order's sum, its customer's row, their name
    c_order = np.argsort(ck[cmask], kind="stable")
    pos = c_order[np.searchsorted(ck[cmask][c_order], cust)]
    assert (ck[cmask][pos] == cust).all()
    name = cname[cmask][pos]
    order = np.lexsort((okey, date, -price))[:limit]
    return [(str(name[i]), int(cust[i]), int(okey[i]), int(date[i]),
             float(price[i]), float(total[okey[i]])) for i in order]
