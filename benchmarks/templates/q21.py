"""TPC-H Q21, the suppliers who kept orders waiting: for one nation's
suppliers, the late lines (received after the commit date) of finished
orders ('F') in which ANOTHER supplier has a line (EXISTS) and no other
supplier has a late one (NOT EXISTS): each subquery correlated on the
order key with an inequality on the supplier key, against all of
lineitem; counted by supplier, the hundred that kept most orders waiting
first.

The SQL text is the benchmark's own (Presto's
``sql/presto/tpch/q21.sql`` with a named hole). ``reference`` is plain
NumPy over the benchmark's own data and shares nothing with the
program: it states EXISTS and NOT EXISTS literally, comparing every pair
of lines of an order.
"""
import numpy as np

from tpchdata_q21 import MAX_LINES, NATIONS, ORDER_STATUS

SQL = """\
select s_name, count(*) as numwait
from supplier, lineitem l1, orders, nation
where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey
  and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate
  and exists (
    select * from lineitem l2
    where l2.l_orderkey = l1.l_orderkey
      and l2.l_suppkey <> l1.l_suppkey)
  and not exists (
    select * from lineitem l3
    where l3.l_orderkey = l1.l_orderkey
      and l3.l_suppkey <> l1.l_suppkey
      and l3.l_receiptdate > l3.l_commitdate)
  and s_nationkey = n_nationkey and n_name = '{NATION}'
group by s_name
order by numwait desc, s_name
limit 100"""

#: TPC-H specification clause 2.4.21.3, written from memory
ASSUMED = {
    "NATION": "one of the 25 values of n_name (clause 4.2.3's list), "
              "drawn uniformly (benchto's q21.sql fixes SAUDI ARABIA)",
    "answer": "a nation has ~4,000 of SF10's 100,000 suppliers and "
              "nearly every one of them kept some order waiting, so "
              "limit 100 binds; s_name is unique and is the ORDER BY's "
              "last key, so the order is total",
}

KINDS = ("string", "int")

#: the answer holds no DOUBLE cell (names and counts, every one exact):
#: the gap the harness reports is 0.0 in every run and there is no limit
#: to set between two readings; the harness reads the name all the same
DOUBLE_REL_LIMIT = 0.0

SCAN_COLUMNS = {
    "lineitem": {"l_orderkey": 8, "l_suppkey": 8, "l_commitdate": 4,
                 "l_receiptdate": 4},
    "orders": {"o_orderkey": 8, "o_orderstatus": 4},
    "supplier": {"s_suppkey": 8, "s_name": 4, "s_nationkey": 8},
    "nation": {"n_nationkey": 8, "n_name": 4},
}

LIMIT = 100


def draw(rng) -> dict:
    return {"NATION": rng.choice(NATIONS)}


def reference(data, sf: float, bindings) -> list:
    n_supp = data.row_counts(sf)["supplier"]
    finished = ORDER_STATUS.index("F")

    def part(li, lo, hi):
        """Lines that count, by supplier key, of the orders lo..hi-1:
        every order padded to MAX_LINES lines, every pair of its lines
        compared."""
        lines = li["lines"]
        n = len(lines)
        first = np.cumsum(lines) - lines
        row = np.repeat(np.arange(n), lines)
        slot = np.arange(len(row)) - np.repeat(first, lines)
        supp = np.zeros((n, MAX_LINES), dtype=np.int64)
        late = np.zeros((n, MAX_LINES), dtype=bool)
        there = np.zeros((n, MAX_LINES), dtype=bool)
        supp[row, slot] = li["l_suppkey"]
        late[row, slot] = li["l_receiptdate"] > li["l_commitdate"]
        there[row, slot] = True
        # other[o, i, j]: line j of order o is there and is another
        # supplier's than line i
        other = there[:, None, :] & (supp[:, None, :] != supp[:, :, None])
        exists = other.any(axis=2)
        exists_late = (other & late[:, None, :]).any(axis=2)
        status = data.orders(sf, lo, hi)["o_orderstatus"]
        keep = (there & late & exists & ~exists_late
                & (status == finished)[:, None])
        return np.bincount(supp[keep], minlength=n_supp + 1)

    waits = np.sum(data.map_lineitem(part, sf), axis=0)
    sup = data.supplier(sf, 1, n_supp + 1)
    nat = data.nation()
    answers = []
    for b in bindings:
        (nk,) = nat["n_nationkey"][nat["n_name"] == b["NATION"]]
        rows = [(str(sup["s_name"][i]), int(waits[k]))
                for i, k in enumerate(sup["s_suppkey"])
                if sup["s_nationkey"][i] == nk and waits[k] > 0]
        answers.append(sorted(rows, key=lambda r: (-r[1], r[0]))[:LIMIT])
    return answers
